def cost(results, operands) -> float:
    M, K = operands[0][1]
    N = operands[1][1][1]
    return 2.0 * M * K * N
