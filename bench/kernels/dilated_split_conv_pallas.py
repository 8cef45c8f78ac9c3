def cost(results, operands) -> float:
    # out (B, F, C); weights (k, C/2, C/2): a k-tap conv on half the channels
    B, F, _ = results[0][1]
    k, cin, cout = operands[1][1]
    return 2.0 * B * F * k * cin * cout
