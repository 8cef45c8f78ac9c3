from readers import roofline_pct


def read(ctx):
    return roofline_pct(ctx, "linear_attention_step_pallas")
