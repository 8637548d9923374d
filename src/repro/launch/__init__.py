"""Launch layer: production meshes, multi-pod dry-run, train/serve drivers,
roofline extraction, the entry points' compile cache. NOTE: import
``dryrun`` only as __main__ or in a fresh process — it forces 512 CPU host
devices and disables the Shardy partitioner."""
from . import mesh, roofline
from .mesh import make_cpu_mesh, make_production_mesh

__all__ = ["mesh", "roofline", "make_cpu_mesh", "make_production_mesh"]
