from .server import GraphQueryServer

__all__ = ["GraphQueryServer"]
