"""The second model's required work, from a step's own record."""

from benchmark.harness import registry

flagship = registry.load_part("counts", "flagship")

prefill_flops = flagship.prefill_flops


def step_flops(dims: dict, slots: list) -> float:
    return sum(accepted * flagship.token_flops(dims, position + 1, True)
               for position, accepted in slots)


def step_bytes(dims: dict, slots: list) -> float:
    return flagship.decode_step_bytes(dims, [p for p, _ in slots])
