def matches(family: str) -> bool:
    return family.startswith("linear_attention")


def cost(results, operands) -> float:
    # q, k, v (BH, L, D): K^T V then Q (K^T V), two (L, D, D) products
    BH, L, D = operands[0][1]
    return 4.0 * BH * L * D * D
