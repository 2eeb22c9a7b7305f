"""Model FLOPs per token (the configuration's reference module counts
them from its shapes; recomputation is left out) times the window's
tokens per second, over the cell's chips times the chip's bf16 peak."""


def read(ctx):
    return 100.0 * ctx["flops_per_token"] * ctx["window"]["tokens"] \
        / ctx["window"]["seconds"] / (ctx["chips"] * ctx["peak_flops"])
