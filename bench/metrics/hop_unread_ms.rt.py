from program import hop_part_ms


def read(ctx):
    return hop_part_ms("unread")
