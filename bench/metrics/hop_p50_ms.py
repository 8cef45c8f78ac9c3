from readers import hop_pct


def read(ctx):
    return hop_pct(ctx, 50)
