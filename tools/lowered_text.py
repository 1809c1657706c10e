#!/usr/bin/env python
"""The lowered text of every program a benchmark cell runs, as a hash, and
whether another tree lowers to the same.

Usage:
    python tools/lowered_text.py [--cell NAME ...] [--against DIR] [--json]

A refactor of the model is safe against a cell's compile cache and its
``setup_s`` exactly when the programs it lowers are the parent's, and that
can be read on a CPU before any chip run (PERF.md, PRs 28, 30, 31). For each
cell of ``BENCHMARK.json`` this reads the cell's configuration and traffic
files, makes abstract parameters and an abstract cache with
``jax.eval_shape`` (no weights exist: the tool holds shapes), lowers the
cell's programs from the package's own factories at the cell's own shapes
and prints ``cell program sha256[:16]`` of ``lower(...).as_text()``:

- a serve cell: the engine's hot program (``jit_step``, or
  ``jit_block_step`` under block-diffusion generation) and ``jit_prefill``
  at every bucket between the one its shortest and the one its longest
  prompt takes, built as ``DecodeEngine.__init__`` builds them;
- a train cell: the step its model's ``build_train`` makes, on the first
  ``chips`` devices (the CPU backend is given four).

What the benchmark knows of an architecture comes through its model's file
(``dims_of``, ``seed_key`` and, where it has one, ``block_spec``).

``--against DIR`` lowers the tree at DIR as well (a ``git archive`` export
of the parent: its package, its benchmark files, this script) and prints
which programs differ; the exit code is 1 if any does. The text holds no
device name, so the hashes of a CPU lowering and of one on the chip agree
only among themselves: compare two trees on one machine.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys

HERE = os.path.abspath(__file__)
REPO_ROOT = os.path.dirname(os.path.dirname(HERE))
MESH_DEVICES = 4


def _digest(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


def prefill_buckets(min_bucket: int, max_len: int, shortest: int,
                    longest: int) -> list:
    """The engine's buckets (doubling from ``min_bucket``, then ``max_len``)
    from the one the shortest prompt takes to the one the longest takes."""
    buckets, b = [], max(2, int(min_bucket))
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    take = lambda n: next((b for b in buckets if b >= n), max_len)  # noqa: E731
    return [b for b in buckets if take(shortest) <= b <= take(longest)]


def serve_programs(cell: dict, model) -> dict:
    """{program: lowered} of a serve cell, the programs and their arguments
    as ``DecodeEngine`` makes and calls them."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.models import transformer_lm as lm
    from deeplearning4j_tpu.serve.quant import (
        activation_dtype,
        dequantize_tree,
        prepare_serve_params,
    )

    config, traffic = cell["config_data"], cell["traffic_data"]
    d, s = model.dims_of(config), config["serve"]
    spec = (model.block_spec(d) if hasattr(model, "block_spec")
            else lm.FLAGSHIP_SPEC)
    params = jax.eval_shape(
        lambda k: prepare_serve_params(lm.init_lm_params(
            k, d["vocab"], d["d_model"], d["n_heads"], d["n_experts"],
            d["d_ff"], d["n_layers"], spec=spec,
            init_scale=d.get("init_scale")), s["serve_dtype"]),
        model.seed_key(0))
    n_slots, max_len = int(s["n_slots"]), int(s["max_len"])
    cache = jax.eval_shape(lambda: lm.init_kv_cache(
        d["n_layers"], n_slots, spec.kv_heads(d["n_heads"]),
        spec.head_size(d["d_model"], d["n_heads"]), max_len,
        dtype=activation_dtype(s["serve_dtype"])))
    programs = dict(params_transform=dequantize_tree, spec=spec)
    key = jax.random.PRNGKey(0)
    positions = np.zeros((n_slots,), np.int32)
    temps = np.zeros((n_slots,), np.float32)
    out = {}
    if spec.generation == "block_diffusion":
        width = spec.block_length
        out["jit_block_step"] = lm.make_block_step(
            d["n_heads"], d["top_k"], **programs).lower(
                params, cache, np.zeros((n_slots, width), np.int32),
                positions, np.zeros((n_slots, width), bool), temps, key, 0)
    else:
        out["jit_step"] = lm.make_decode_step(
            d["n_heads"], d["top_k"], **programs).lower(
                params, cache, np.zeros((n_slots,), np.int32), positions,
                temps, key, 0)
    prefill = lm.make_prefill_step(d["n_heads"], d["top_k"], attn_impl=None,
                                   **programs)
    lens = traffic["prompt_len"]
    for b in prefill_buckets(s["min_bucket"], max_len, int(lens["min"]),
                             int(lens["max"])):
        # graftlint: allow[prng-reuse] lowered, never run: nothing is drawn
        out[f"jit_prefill[{b}]"] = prefill.lower(
            params, cache, np.zeros((1, b), np.int32), b - 1, 0,
            np.float32(0.0), key, 0)
    return out


def train_programs(cell: dict, model) -> dict:
    """{program: lowered} of a train cell: the step of the model's
    ``build_train``, with its parameters placed as that places them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models import transformer_lm as lm

    config, traffic = cell["config_data"], cell["traffic_data"]
    d, tr = model.dims_of(config), config["train"]
    batch, seq = int(traffic["batch_sequences"]), int(traffic["seq_len"])
    params = jax.eval_shape(
        lambda k: lm.init_lm_params(k, d["vocab"], d["d_model"], d["n_heads"],
                                    d["n_experts"], d["d_ff"], d["n_layers"]),
        model.seed_key(0))
    rows = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    common = dict(lr=tr["lr"], top_k=d["top_k"], aux_weight=tr["aux_weight"],
                  donate=True, tuned=False, runprof=False)
    if "mesh" not in tr:
        step = lm.make_single_device_train_step(d["n_heads"], **common)
        return {"jit_step": step.lower(params, rows, rows)}
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()[:cell["chips"]]
    if len(devices) < cell["chips"]:
        raise RuntimeError(f"{cell['name']} needs {cell['chips']} devices, "
                           f"this backend has {len(devices)}")
    mesh = Mesh(np.array(devices).reshape(tuple(tr["mesh"].values())),
                tuple(tr["mesh"]))
    placed = jax.tree_util.tree_map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        params, lm.lm_param_shardings(params, mesh))
    rows = jax.ShapeDtypeStruct(rows.shape, rows.dtype, sharding=NamedSharding(
        mesh, P(*(a if a in mesh.axis_names else None
                  for a in (lm.DATA_AXIS, lm.SEQ_AXIS)))))
    step = lm.make_composed_train_step(
        mesh, d["n_heads"], batch * seq // tr["mesh"]["data"], **common)
    return {"jit_step": step.lower(placed, rows, rows)}


def cell_hashes(cell: dict) -> dict:
    """{program: hash} of one cell as ``registry.load_cell`` read it (a
    test lays the files' ``rehearse`` blocks over it first)."""
    from benchmark.harness import registry

    model = registry.load_model(cell)
    kind = cell["traffic_data"]["kind"]
    lowered = (serve_programs if kind == "serve" else train_programs)(
        cell, model)
    return {name: _digest(low) for name, low in lowered.items()}


def tree_hashes(cells=None) -> dict:
    """{cell: {program: hash}} of the tree this script's ``benchmark`` and
    ``deeplearning4j_tpu`` are imported from."""
    from benchmark.harness import registry

    bench = registry.load_benchmark()
    names = cells or [c["name"] for c in bench["workloads"]]
    return {n: cell_hashes(registry.load_cell(bench, n)) for n in names}


def other_tree_hashes(root: str, cells=None) -> dict:
    """``tree_hashes`` of the tree at ``root``, by this script in a process
    of its own whose imports resolve there."""
    cmd = [sys.executable, HERE, "--root", root, "--json"]
    for name in cells or []:
        cmd += ["--cell", name]
    done = subprocess.run(cmd, capture_output=True, text=True, cwd=root)
    if done.returncode:
        raise RuntimeError(f"lowering {root} failed:\n{done.stderr[-4000:]}")
    return json.loads(done.stdout.splitlines()[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", action="append",
                    help="a workload of BENCHMARK.json (default: every one)")
    ap.add_argument("--against", metavar="DIR",
                    help="a second tree to lower and compare with")
    ap.add_argument("--root", default=REPO_ROOT, help=argparse.SUPPRESS)
    ap.add_argument("--json", action="store_true",
                    help="one JSON object instead of lines")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.root))
    import jax

    # the mesh step needs four devices; the setting touches the CPU
    # backend alone and a chip's own count stands
    jax.config.update("jax_num_cpu_devices", MESH_DEVICES)
    mine = tree_hashes(args.cell)
    if args.json:
        print(json.dumps(mine))
    other = other_tree_hashes(args.against, args.cell) if args.against \
        else None
    differ = 0
    for cell, programs in mine.items():
        for name, digest in programs.items():
            line = f"{cell} {name} {digest}"
            if other is not None:
                theirs = other.get(cell, {}).get(name)
                differ += theirs != digest
                line += " equal" if theirs == digest else f" DIFFERS {theirs}"
            if not args.json:
                print(line)
    if other is not None and not args.json:
        print(f"{differ} of {sum(map(len, mine.values()))} programs differ")
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
