"""Left pads as a share of the prefilled width over the window's ``serve.prefill`` spans: sum(width - min(n_prompt, width)) / sum(width), %."""
from benchkit import spans


def read(ctx):
    return spans.of(ctx, spans.prefill_pad_share)
