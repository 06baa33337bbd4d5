from .mesh import auto_mesh, make_device_mesh, make_production_mesh

__all__ = ["auto_mesh", "make_production_mesh", "make_device_mesh"]
