"""A·Aᵀ·B, arXiv:2207.02070 §3.2.2: A is d0×d1, B is d0×d2.

The plain reference, the operands and the closed-form work of the five
algorithms the paper enumerates. Nothing here comes from the program.
"""


def operand_shapes(d):
    """Operand shapes by leaf base (A is base 0, B is base 2)."""
    d0, d1, d2 = d
    return {0: (d0, d1), 2: (d0, d2)}


def call_args(ops):
    """The planner's positional arguments: one per chain leaf (A, Aᵀ, B)."""
    return (ops[0], ops[0], ops[2])


def reference(ops, matmul, store):
    """(A·Aᵀ)·B; ``store`` rounds every array that is stored."""
    a, b = store(ops[0]), store(ops[2])
    return store(matmul(store(matmul(a, a.T)), b))


def algorithm_flops(d):
    """FLOPs of each algorithm by the paper's counts (§3.1)."""
    d0, d1, d2 = d
    syrk_symm = (d0 + 1) * d0 * d1 + 2 * d0 * d0 * d2
    gemm_gemm = 2 * d0 * d0 * d1 + 2 * d0 * d0 * d2
    return {
        "alg1[syrk+symm]": syrk_symm,
        "alg2[syrk+tri2full+gemm]": syrk_symm,
        "alg3[gemm+symm]": gemm_gemm,
        "alg4[gemm+gemm]": gemm_gemm,
        "alg5[gemm+gemm]": 4 * d0 * d1 * d2,   # (Aᵀ·B) first
    }


def compulsory_elements(d):
    """Elements every algorithm must move: the inputs and the output."""
    d0, d1, d2 = d
    return d0 * d1 + d0 * d2 + d0 * d2
