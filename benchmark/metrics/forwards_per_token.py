"""Slot-forwards of the block step (``serve_block_forwards_total``, denoising
and commit) over the positions that left the mask
(``serve_block_tokens_accepted_total``), across the window: (D + 1) / B under
the configuration's schedule, a little more where a prompt's remainder opens
a block."""


def read(run):
    c = run["summary"]["counters"]
    if not c.get("tokens_accepted"):
        return None
    return c["block_forwards"] / c["tokens_accepted"]
