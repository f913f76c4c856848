from bench.steplog import kv_pages_ms as read  # noqa: F401
