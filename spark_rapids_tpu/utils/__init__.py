from .tracing import annotate_op  # noqa: F401
