"""Model FLOP/s of serving over the chip's bf16 peak (%): 2 FLOPs per weight
a token passes (layers and head; ``counts.decoder_flops_per_token`` with
the attention over the context left out), times the prompt and output
tokens processed in the window, over the window."""


def read(ctx):
    f = ctx["facts"]
    if "flops_per_token" not in f:
        return None
    rate = f["flops_per_token"] * (f["tokens"] + f["prompt_tokens"]) / f["window_s"]
    return 100.0 * rate / (ctx["peaks"]["bf16_flops"] * ctx["trace"]["chips"])
