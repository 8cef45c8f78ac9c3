from readers import hops_per_step as read  # noqa: F401
