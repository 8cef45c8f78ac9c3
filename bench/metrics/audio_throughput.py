from readers import audio_throughput as read  # noqa: F401
