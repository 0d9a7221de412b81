"""Each work counter against a count worked by hand."""

import registry

W8A = {"n_clients": 60, "samples_per_client": 829, "dim": 267, "dtype": "float32"}
RCV1 = {"n_clients": 20, "samples_per_client": 1012, "dim": 47236, "dtype": "float32"}


def test_dense_round_counts_hessian_gradient_and_kernel_iterations():
    hp = {"hessian_period": 1, "eq9_cg_iters": 32}
    flops, nbytes = registry.work("fednew_round").work(W8A, hp)
    hessian = 2 * 60 * 829 * 267 * 267  # A^T D A per client: 7,091,829,720
    gradient = 4 * 60 * 829 * 267  # 53,122,320
    solve = 32 * 60 * 2 * 267 * 267  # 273,749,760
    assert flops == hessian + gradient + solve == 7_418_701_800
    assert nbytes is None


def test_dense_round_amortises_the_hessian_over_its_period():
    full = registry.work("fednew_round").work(W8A, {"hessian_period": 1, "eq9_cg_iters": 32})[0]
    lazy = registry.work("fednew_round").work(W8A, {"hessian_period": 10, "eq9_cg_iters": 32})[0]
    assert full - lazy == 0.9 * 2 * 60 * 829 * 267 * 267


def test_matfree_round_counts_one_feature_sweep_per_cg_iteration():
    # Default matmul precision: the matrix unit takes bfloat16 operands,
    # so a sweep reads 2 bytes a feature.
    flops, nbytes = registry.work("fednew_round").work(
        RCV1, {"hessian_repr": "matfree", "cg_iters": 8, "operand_bytes": 2})
    sweep = 20 * 1012 * 47236  # 956,056,640 features
    assert flops == 4 * sweep + 8 * 4 * sweep
    assert nbytes == 8 * 2 * sweep == 15_296_906_240


def test_client_solve_kernel_reads_hessians_and_vectors_once():
    flops, nbytes = registry.work("client_solve").work(W8A, {"eq9_cg_iters": 32})
    assert flops == 32 * 60 * 2 * 267 ** 2
    assert nbytes == 4 * (60 * 267 ** 2 + 2 * 60 * 267) == 17_237_520


def test_stoch_quant_kernel_moves_five_words_per_coordinate():
    flops, nbytes = registry.work("stoch_quant").work(W8A, {})
    assert nbytes == 4 * (5 * 60 * 267 + 60) == 320_640
    assert flops == 10 * 60 * 267
