from readers import roofline_pct


def read(ctx):
    return roofline_pct(ctx, "dilated_split_conv_pallas")
