"""A second model, for ``test_second_model_is_files_only.py`` alone: the
flagship block behind a configuration that spells its sizes with other keys
(``moe_intermediate_size`` beside an unused ``intermediate_size``,
``head_dim``, ``num_key_value_heads``), with a reference and counts of its
own and a fault planted in another program than the flagship's. The test
lays these files over a copy of the benchmark; no cell of BENCHMARK.json
names them.
"""

from benchmark.harness import registry

base = registry.load_part("models", "flagship")
ref = registry.load_part("reference", "otherkeys_ref")
counts = registry.load_part("counts", "otherkeys")

dims_of = ref.dims_of
seed_key = base.seed_key
serve_counters = base.serve_counters
serve_steps = base.serve_steps
serve_programs = base.serve_programs
program_scopes = base.program_scopes
prefill_flops = counts.prefill_flops
step_flops = counts.step_flops
step_bytes = counts.step_bytes


def build_serve(config: dict, seed: int, metrics):
    return base.build_serve(ref.flagship_keys(config), seed, metrics)


def serve_compare(config: dict, traffic: dict, seed: int, sampled: list,
                  control_via=None) -> dict:
    return base.serve_compare(ref.flagship_keys(config), traffic, seed,
                              sampled, control_via)


def faults(cell: dict) -> dict:
    return {"alter_a_token": _alter_a_first_token}


def _alter_a_first_token(driver) -> None:
    """A token altered where it is produced, in the prefill program: every
    second prompt's first token comes back as the next id."""
    prefill, calls = driver.engine._prefill, [0]
    vocab = driver.dims["vocab"]

    def broken(*args):
        cache, tok = prefill(*args)
        calls[0] += 1
        return cache, (tok + calls[0] % 2) % vocab

    driver.engine._prefill = broken
