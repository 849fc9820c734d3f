"""The matrix chain A·B·C·D, arXiv:2207.02070 §3.2.1, over d0..d4.

The plain reference, the operands and the closed-form work of the six
association orders the paper enumerates. Nothing here comes from the
program.
"""


def operand_shapes(d):
    """Operand shapes by leaf base: A, B, C, D are bases 0 to 3."""
    return {i: (d[i], d[i + 1]) for i in range(4)}


def call_args(ops):
    return (ops[0], ops[1], ops[2], ops[3])


def reference(ops, matmul, store):
    """((A·B)·C)·D; ``store`` rounds every array that is stored."""
    a, b, c, d = (store(ops[i]) for i in range(4))
    return store(matmul(store(matmul(store(matmul(a, b)), c)), d))


def algorithm_flops(d):
    """FLOPs of each order, 2·m·n·k per product (paper §3.1)."""
    d0, d1, d2, d3, d4 = d

    def g(m, n, k):
        return 2 * m * n * k

    ab_cd = g(d0, d2, d1) + g(d2, d4, d3) + g(d0, d4, d2)
    return {
        "alg1[gemm+gemm+gemm]": g(d0, d2, d1) + g(d0, d3, d2) + g(d0, d4, d3),
        "alg2[gemm+gemm+gemm]": ab_cd,          # A·B first
        "alg3[gemm+gemm+gemm]": g(d1, d3, d2) + g(d0, d3, d1) + g(d0, d4, d3),
        "alg4[gemm+gemm+gemm]": g(d1, d3, d2) + g(d1, d4, d3) + g(d0, d4, d1),
        "alg5[gemm+gemm+gemm]": ab_cd,          # C·D first
        "alg6[gemm+gemm+gemm]": g(d2, d4, d3) + g(d1, d4, d2) + g(d0, d4, d1),
    }


def compulsory_elements(d):
    """Elements every order must move: the four inputs and the output."""
    return sum(d[i] * d[i + 1] for i in range(4)) + d[0] * d[4]
