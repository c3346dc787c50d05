"""Model FLOP/s utilisation: the operations the forward and backward
passes REQUIRE per token (benchmarks/kernels/shapes.train_flops_per_token:
matmul parameters only, no embedding gather, no recomputation) times
tokens per second per chip, over the chip's bf16 peak."""


def read(obs):
    if not obs.get("step_s") or not obs.get("peaks"):
        return None
    return 100.0 * obs["flops_per_token"] * obs["tokens_per_s_per_chip"] \
        / obs["peaks"]["bf16_flops_per_s"]
