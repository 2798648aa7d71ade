"""Operations and bytes the algorithm needs, from the configuration's sizes.

These are the numerators of every utilisation share the benchmark reports
(`mxu_share.vit`, `hbm_share.decode`). They count what the mathematics
needs (2 operations for a multiply-accumulate), not what a program happens
to execute: recomputed or padded work does not count. `config` is a
configuration file under `benchmark/configs/` as loaded."""


def vit_positions(config):
    side = config["image_size"] // config["patch_size"]
    return side * side + 1          # patches and the class token


def vit_forward_flops(config):
    """FLOPs of one image's forward pass through a ViT encoder and head."""
    d, inner = config["hidden_size"], config["intermediate_size"]
    n, blocks = vit_positions(config), config["num_hidden_layers"]
    patch_in = config["patch_size"] ** 2 * config["num_channels"]
    embed = 2 * (n - 1) * patch_in * d
    per_block = (2 * n * d * 3 * d          # query, key, value projections
                 + 2 * n * n * d            # scores, all heads together
                 + 2 * n * n * d            # weighted values
                 + 2 * n * d * d            # output projection
                 + 2 * 2 * n * d * inner)   # the two MLP matmuls
    head = 2 * d * config["num_labels"]
    return embed + blocks * per_block + head


def gpt2_matmul_params(config):
    """Weights a decode step multiplies by: the blocks and the tied head."""
    d, inner = config["n_embd"], config["n_inner"] or 4 * config["n_embd"]
    per_block = 3 * d * d + d * d + 2 * d * inner
    return config["n_layer"] * per_block + config["vocab_size"] * d


def gpt2_cache_bytes_per_token(config, itemsize=2):
    """Key and value of one cached position over every block."""
    return 2 * config["n_layer"] * config["n_embd"] * itemsize


def gpt2_decode_step_bytes(config, rows, live_positions, itemsize=2):
    """Bytes one decode step must read from HBM: every matmul weight once
    (the step's rows share them) and each row's live cache."""
    return (gpt2_matmul_params(config) * itemsize
            + rows * live_positions * gpt2_cache_bytes_per_token(config,
                                                                 itemsize))


def gpt2_decode_step_flops(config, rows, live_positions):
    """FLOPs of one decode step: the weights' matmuls and the attention
    over the live cache (scores and weighted values)."""
    d = config["n_embd"]
    attend = config["n_layer"] * 2 * 2 * live_positions * d
    return rows * (2 * gpt2_matmul_params(config) + attend)
