from program import span_ms


def read(ctx):
    return span_ms("deliver")
