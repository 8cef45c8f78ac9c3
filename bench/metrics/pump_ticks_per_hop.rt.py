from readers import pump_ticks_per_hop as read  # noqa: F401
