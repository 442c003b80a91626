"""lm_mfu: the whole model's share (%) of the chip's bf16 peak: the
operations of every token processed in the window (each prompt token of
the prefills that finished in it, each output token emitted in it), by
the benchmark's own count per token (``token_flops`` of the
configuration's family under ``bench/families``), over the window and
the peak."""
from benchlib import files, peaks, stats


def read(ctx):
    clock = ctx.system.tokens
    n_pre = clock.prefills_in(ctx.t0, ctx.t1)
    # a prefill processes the whole prompt and emits the first token;
    # every other output token is one decoded token
    n_tokens = (ctx.system.prompt_len * n_pre
                + clock.tokens_in(ctx.t0, ctx.t1) - n_pre)
    if n_tokens <= 0:
        return None
    family = files.module("families", ctx.cfg["family"])
    work = n_tokens * family.token_flops(ctx.cfg)
    peak = peaks.peaks(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * stats.rate(work, ctx.t0, ctx.t1) / peak
