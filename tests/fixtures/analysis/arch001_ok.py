"""ARCH001 clean: version-sensitive APIs routed through the shims."""
from repro.kernels.pallas_compat import CompilerParams, resolve_interpret
from repro.launch.mesh import auto_mesh


def launch(shape):
    return CompilerParams, resolve_interpret(None), auto_mesh(shape, ("dp",))
