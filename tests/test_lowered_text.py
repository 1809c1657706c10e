"""``tools/lowered_text.py``: the lowered text of a cell's programs as the
check that a refactor left them alone. Every cell of BENCHMARK.json cut to
its files' ``rehearse`` widths: two lowerings of one tree agree, an edited
block is seen in every program, the serve programs are the ones the engine
itself lowers, and ``--against`` names what differs in a second tree.
"""

import hashlib
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests", "benchmark")]

from benchmark.harness import registry  # noqa: E402
from deeplearning4j_tpu.models import transformer_lm as lm  # noqa: E402
from test_cells_rehearse import BENCH, CELLS, tiny_cell  # noqa: E402
from tools import lowered_text  # noqa: E402

SERVE_CONFIGS = sorted({c["config"]: c["name"] for c in BENCH["workloads"]
                        if registry.load_cell(BENCH, c["name"])[
                            "traffic_data"]["kind"] == "serve"}.values())


@pytest.mark.parametrize("name", CELLS)
def test_one_tree_lowers_the_same_twice_and_an_edited_block_differs(
        name, monkeypatch):
    cell = tiny_cell(name)
    first = lowered_text.cell_hashes(cell)
    assert len(first) >= 1 and first == lowered_text.cell_hashes(cell)

    block = lm._block

    def edited(*args, **kwargs):
        h, kept, flat = block(*args, **kwargs)
        return h * 2.0, kept, flat

    monkeypatch.setattr(lm, "_block", edited)
    after = lowered_text.cell_hashes(cell)
    assert after.keys() == first.keys()
    assert all(after[program] != first[program] for program in first), after


@pytest.mark.parametrize("name", SERVE_CONFIGS)
def test_serve_programs_are_the_ones_the_engine_lowers(name):
    """The tool builds the programs from the factories as
    ``DecodeEngine.__init__`` does and calls them as the tick does: held to
    the engine's own, from real weights, at test widths."""
    from deeplearning4j_tpu.telemetry.registry import MetricsRegistry

    cell = tiny_cell(name)
    e = registry.load_model(cell).build_serve(cell["config_data"], 3,
                                              MetricsRegistry())
    if e.block_mode:
        hot = {"jit_block_step": e._block_step.lower(
            e.params, e._cache, e._block_tokens, e._positions,
            e._block_masked, e._temps, e._key, e._step_idx)}
    else:
        hot = {"jit_step": e._decode.lower(
            e.params, e._cache, e._tokens, e._positions, e._temps, e._key,
            e._step_idx)}
    got = lowered_text.cell_hashes(cell)
    for program, lowered in hot.items():
        assert got[program] == hashlib.sha256(
            lowered.as_text().encode()).hexdigest()[:16]
    prompts = cell["traffic_data"]["prompt_len"]
    assert {f"jit_prefill[{e.bucket_for(n)}]"
            for n in (prompts["min"], prompts["max"])} <= got.keys()
    assert set(got) - set(hot) <= {f"jit_prefill[{b}]" for b in e._buckets}


def test_against_names_the_programs_an_edit_moved(tmp_path):
    """The command line on a second tree, here a copy with one line of the
    block edited: every program of the cell differs and the exit code says
    so; against the tree itself nothing does."""
    cell = "serve-blockdiff-sat"
    other = tmp_path / "edited"
    other.mkdir()
    for part in ("deeplearning4j_tpu", "benchmark"):
        shutil.copytree(os.path.join(ROOT, part), other / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), other)
    source = other / "deeplearning4j_tpu" / "models" / "transformer_lm.py"
    line = 'h2 = _norm(p, "ln2", h, spec)\n'
    text = source.read_text()
    assert text.count(line) == 1
    source.write_text(text.replace(line, line[:-1] + " * 2.0\n"))

    def run(against):
        return subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "lowered_text.py"),
             "--cell", cell, "--against", str(against)],
            capture_output=True, text=True, timeout=600,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))

    moved = run(other)
    lines = moved.stdout.splitlines()
    assert moved.returncode == 1, moved.stderr[-2000:]
    assert lines[-1] == "5 of 5 programs differ"
    assert all(" DIFFERS " in text for text in lines[:-1])
    same = run(ROOT)
    assert same.returncode == 0, same.stderr[-2000:]
    assert same.stdout.splitlines()[-1] == "0 of 5 programs differ"
