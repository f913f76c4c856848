from bench.steplog import memctl_jobs_per_token as read  # noqa: F401
