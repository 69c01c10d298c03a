"""Window modes, one module per traffic ``mode``."""
