"""Device time of the block-diffusion step (``jit_block_step``) in the trace
over the number of its runs there."""


def read(run):
    t = run["trace"]
    calls = t["program_calls"].get("jit_block_step", 0)
    if not calls:
        return None
    return 1000.0 * t["by_program"]["jit_block_step"] * t["devices"] / calls
