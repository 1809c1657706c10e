"""Continuous-batching decode engine for the composed transformer LM.

The serving half of the flagship (ISSUE 10; ROADMAP 2 — the DL4J
train/test/predict + UI layer reborn as a model server). One engine owns:

- a **fixed-slot KV cache** (models/transformer_lm.init_kv_cache): S pages
  of (L, H, T_max, Dh) keys/values, one per concurrent request;
- ONE jitted **decode executable** (make_decode_step) whose shapes are
  pinned at S — every iteration advances EVERY slot one token (inactive
  slots carry masked garbage), so occupancy changes never retrace and the
  steady-state decode loop holds a 0-compile budget
  (tests/test_serve.py);
- a family of **prefill executables** (make_prefill_step), one per prompt
  bucket (powers of two up to ``max_len``): admission pads the prompt to
  its bucket, runs the full-prompt pass through the ``attn_impl`` seam
  (blockwise flash for long prompts), seeds the slot's cache page, and
  samples the first token — one dispatch per admission.

Scheduling is Orca-style iteration-level continuous batching: each
``step()`` first admits queued requests into free slots (prefill), then
runs one fused decode step; requests are retired **per decode step** at
EOS / ``max_new_tokens`` / cache-page exhaustion, and the freed slot is
reusable on the very next iteration — no batch barrier, a short request
never waits for a long one.

Weights arrive either directly (``DecodeEngine(params, n_heads)``), from
a sharded checkpoint via the resharding loader
(``DecodeEngine.from_checkpoint`` → ``Checkpointer.restore`` — any
save-time mesh restores onto the serving host), or from a LIVE
device-resident tree (``DecodeEngine.from_live_params`` — ISSUE 14: the
adoption runs through the in-graph redistribution plans of
``scaleout.ckpt.redistribution``, device-to-device, no host gather). The ``serve_dtype=`` seam
(serve/quant.py) prepares them: bf16 by default, ``"int8"`` for the
weight-only-quantized A/B twin, ``None``/``"f32"`` for the parity
precision.

Telemetry flows through the PR 2 registry under ``serve_*`` (queue depth,
slot occupancy, token/request counters, prefill/decode/request latency
histograms) and is served by ``UiServer`` at ``/api/serve``. Every decode
dispatch counts the chunks of the cache its attention reads against the
chunks there are (``serve_attn_chunks_read_total`` over
``serve_attn_chunks_total``, from ``_positions`` by
``transformer_lm.attn_chunk_reads``), and its ``tick.decode`` phase carries
the read count as ``chunks``.

Block-diffusion generation (ISSUE 28): a model whose ``BlockSpec`` says
``generation="block_diffusion"`` is served by the same queue, slots,
buckets, admission and retirement, with another tick. A sequence is laid out
in blocks of B from position 0; admission prefills the prompt's whole blocks
(the prefill stores and samples nothing) and the prompt's remainder opens
the first generated block. A tick then runs ONE ``jit_block_step`` over
every slot: for a slot with masked positions a denoising forward, which
unmasks the ``B // D`` most confident of them on the device and leaves
provisional K/V rows, else a commit forward, which leaves the finished
block's final rows and moves the slot to its next block. A request is handed
a block's tokens, in position order with the step's one stamp, by the
forward that unmasks its last position: 0 to B tokens a slot a tick, through
the accept path of the one-token tick. Every forward is appended to
``ServeRequest.forwards``. Speculation, chunked prefill and the prefix cache
are refused with such a spec.

Request-scoped tracing (ISSUE 12): when a process tracer is configured
(telemetry/trace.py), every request becomes a ``serve.request`` span with
``serve.queue_wait`` / ``serve.prefill`` / ``serve.decode`` /
``serve.retire`` children — per-token ``accept`` events on the decode
span, retire reason + weight version as attributes — and every scheduler
iteration an ``engine.step`` span recording admissions / occupancy /
retirements. Spans parent under the submitting thread's current span
(the UiServer handler's ``http.request`` span, itself parented under an
inbound W3C ``traceparent``), so one trace tree spans loadgen → HTTP →
engine scheduler thread. The begin records are written eagerly, so a
``kill -9`` mid-request leaves open ``serve.request`` spans that
``tools/trace_report.py`` reconstructs, exactly like the elastic rounds.
Unconfigured, all of it is a None-check per call site — zero cost, and
the greedy-parity + 0-compile pins run tracer-armed in test_serve.py.

Serving fast path (ISSUE 16) — three pure-schedule optimizations, each
pinned token-identical to the cold/sequential oracle and each defaulting
OFF:

- ``prefix_cache=``: shared-prefix KV page reuse (serve/prefix_cache.py).
  Admission looks up the longest cached page-aligned prefix, seeds the
  slot's cache rows from the shared pages, and prefills ONLY the uncached
  suffix; a FULL hit (cached prefix covers all but at most the last
  prompt token) issues ZERO flagship prefill dispatches — the last prompt
  token rides the ordinary decode tick, whose write-then-mask math
  computes exactly the prefill's last-position logits. Every flagship
  prefill-shaped dispatch (classic or chunk) counts
  ``serve_prefill_dispatches_total``, which is what the full-hit test
  asserts stays flat.
- ``prefill_chunk=``: long prompts prefill in fixed-width chunks, ONE
  chunk per scheduler iteration interleaved with decode ticks — a long
  admission no longer head-of-line-blocks every running request's next
  token. Chunk shapes are pinned at the configured width (the final
  chunk shifts left to overlap rather than changing shape), so the
  0-compile steady-state budget holds. While a slot is mid-prefill its
  host position points at the next chunk's start, so the shared decode
  dispatch's garbage write for that slot lands where the next chunk
  overwrites it before any query can attend to it.
- ``speculative=`` / ``DL4J_TPU_SERVE_SPEC``: draft/verify speculative
  decoding (serve/speculative.py). A layer-truncated (or distilled)
  draft proposes k tokens per slot via k cheap draft decode dispatches;
  the flagship verifies all k in ONE ``make_verify_step`` dispatch of
  width k+1, and the host accepts the longest matching prefix plus the
  flagship's bonus token — 1 to k+1 tokens per flagship dispatch,
  greedy streams exactly the non-speculative ones. Acceptance lands in
  ``serve_spec_accepted_per_verify`` / the ``serve_spec_accept_rate``
  gauge (watchtower's ``serve_spec_accept_collapse`` rule), verify
  latency in ``serve_verify_step_ms`` with trace exemplars.
"""

from __future__ import annotations

import itertools
import threading
import time
from functools import partial
from typing import List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models.transformer_lm import (
    FLAGSHIP_SPEC,
    BlockSpec,
    attn_chunk_reads,
    draft_truncate_params,
    init_hybrid_cache,
    init_kv_cache,
    lm_dims,
    make_block_step,
    make_chunk_prefill_step,
    make_decode_step,
    make_prefill_step,
    make_verify_step,
    spec_from_meta,
)
from deeplearning4j_tpu.serve.prefix_cache import (
    PrefixPageCache,
    seed_slot_pages,
)
from deeplearning4j_tpu.serve.quant import (
    activation_dtype,
    dequantize_tree,
    params_nbytes,
    prepare_serve_params,
)
from deeplearning4j_tpu.serve.speculative import (
    accept_longest_prefix,
    resolve_speculative,
)
from deeplearning4j_tpu.telemetry import trace as _trace
from deeplearning4j_tpu.telemetry.runprof import StepTiming, resolve_runprof
from deeplearning4j_tpu.utils.lockwatch import make_condition, make_rlock

_UNSET = object()


class ServeRequest:
    """One generation request's lifecycle record. ``done`` is set when the
    request retires; ``generated`` then holds the output tokens (EOS
    excluded) and ``finish_reason`` one of "eos" | "max_new_tokens" |
    "max_len". Timestamps (perf_counter seconds) are the latency
    accounting loadgen/bench read: ``t_submit`` → ``t_first`` (first
    token) → ``t_done``."""

    def __init__(self, rid: int, prompt: List[int], max_new_tokens: int,
                 temperature: float, eos_id: Optional[int]):
        self.rid = rid
        self.prompt = list(prompt)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self.generated: List[int] = []
        self.finish_reason: Optional[str] = None
        self.done = threading.Event()
        self.slot: Optional[int] = None
        self.t_submit: float = 0.0
        self.t_admit: Optional[float] = None
        self.t_first: Optional[float] = None
        self.t_done: Optional[float] = None
        # tracing (ISSUE 12): None unless a process tracer is configured
        # at submit time — every touch below is a None-check when off
        self.span = None          # serve.request (submit → retire)
        self.queue_span = None    # serve.queue_wait (submit → admission)
        self.decode_span = None   # serve.decode (admission → retire)
        # the request's trace id outlives the span (ISSUE 15): latency
        # histogram observations attach it as an exemplar at retire time,
        # after serve.request has already ended
        self.trace_id = None
        self.prefill_ms: float = 0.0
        self.decode_ms: float = 0.0  # sum of decode dispatches it rode
        # fast-path attribution (ISSUE 16): prefill_ms splits into the
        # prefix-cache seed time and the suffix/chunk compute time
        self.prefill_cached_ms: float = 0.0
        self.prefill_suffix_ms: float = 0.0
        self.cached_tokens: int = 0     # prefix-cache-seeded positions
        self.prefill_chunks: int = 0    # chunk dispatches this request ran
        self.prefill_span = None        # serve.prefill (may span steps)
        # per-accepted-token arrival stamps (perf_counter seconds) — the
        # inter-token latency loadgen's p99 reads (chunked-prefill bench)
        self.t_tokens: List[float] = []
        # block-diffusion generation: every forward of this request's slot,
        # ``(stamp, block, kind, masked before, positions accepted,
        # tokens)``: the block's index, "denoise" or "commit", a bool a
        # position, the in-block positions that left the mask, and the
        # block's B tokens after the forward (0 where still masked)
        self.forwards: List[tuple] = []

    @property
    def latency_s(self) -> Optional[float]:
        if self.t_done is None:
            return None
        return self.t_done - self.t_submit


class DecodeEngine:
    """KV-cached autoregressive decode with continuous batching (module
    docstring). Thread-safe: ``submit``/``generate`` may be called from
    any thread (e.g. UiServer handler threads); ``step`` serializes on an
    internal lock. ``start()`` runs the scheduler on a background thread;
    without it, ``generate`` drives the loop inline."""

    def __init__(self, params, n_heads: int, *, n_slots: int = 4,
                 max_len: int = 256, top_k: int = 2,
                 attn_impl: Optional[str] = None,
                 serve_dtype: Optional[str] = "bf16",
                 eos_id: Optional[int] = None, seed: int = 0,
                 registry=None, min_bucket: int = 8,
                 weight_version: Optional[str] = None,
                 prefix_cache=False, prefix_page_tokens: int = 16,
                 prefix_cache_pages: int = 256,
                 prefill_chunk: Optional[int] = None,
                 speculative=None, runprof=None, tuned=None,
                 spec: Optional[BlockSpec] = None):
        from deeplearning4j_tpu.telemetry.registry import default_registry

        # tuned= (ISSUE 20): adopt the autotuner's "serve" seam —
        # min_bucket and slots (scheduling knobs; greedy decode stays
        # token-identical, pinned in tests/test_tune.py). The engine
        # builds its own cache-key context from the param dims it already
        # knows, so a bare tuned=True works here (unlike the step
        # factories, which need tune_context=). Explicit dict > cache >
        # DL4J_TPU_TUNED env > off; a dict also serves as explicit knobs.
        if tuned is not False:
            from deeplearning4j_tpu.tune.cache import resolve_step_tuning
            from deeplearning4j_tpu.tune.seams import serve_context
            ctx = serve_context(lm_dims(params), int(n_heads), int(max_len))
            tuning = resolve_step_tuning(tuned, ctx, ("serve",))
            if "min_bucket" in tuning:
                min_bucket = int(tuning["min_bucket"])
            if "slots" in tuning:
                n_slots = int(tuning["slots"])

        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        if prefill_chunk is not None and not (
                1 <= int(prefill_chunk) < max_len):
            raise ValueError(
                f"prefill_chunk must be in [1, max_len), got "
                f"{prefill_chunk}")
        self.dims = lm_dims(params)
        self.n_heads = int(n_heads)
        # the kind of block the params are (models/transformer_lm.BlockSpec)
        # and, with it, the way the model generates; ``self.spec`` below is
        # the speculative-decoding config, an older name
        self.block_spec = spec if spec is not None else FLAGSHIP_SPEC
        self.block_mode = self.block_spec.generation == "block_diffusion"
        if self.block_spec.head_dim is None and \
                self.dims["d_model"] % self.n_heads:
            raise ValueError(
                f"d_model {self.dims['d_model']} % n_heads {n_heads} != 0")
        # a layer pattern with state-space layers: each slot keeps recurrent
        # state beside its K/V pages
        self.ssm_mode = self.block_spec.layer_pattern is not None
        if self.ssm_mode:
            self._check_state_space(speculative, prefill_chunk, prefix_cache)
        if self.block_mode:
            self._check_block_mode(speculative, prefill_chunk, prefix_cache)
        self.n_slots = int(n_slots)
        self.max_len = int(max_len)
        self.top_k = int(top_k)
        self.serve_dtype = serve_dtype
        self.eos_id = eos_id
        # per-request weight/checkpoint forensics (ISSUE 12; ROADMAP 4's
        # hot-swap will bump this between decode steps): recorded on every
        # serve.retire span and in stats()
        self.weight_version = weight_version
        self.registry = registry if registry is not None else \
            default_registry()
        self.params = prepare_serve_params(params, serve_dtype)
        self.weight_bytes = params_nbytes(self.params)
        head_dim = self.block_spec.head_size(self.dims["d_model"],
                                             self.n_heads)
        n_kv = self.block_spec.kv_heads(self.n_heads)
        make_cache = (partial(init_hybrid_cache, self.block_spec)
                      if self.ssm_mode else init_kv_cache)
        self._cache = make_cache(self.dims["n_layers"], self.n_slots, n_kv,
                                 head_dim, self.max_len,
                                 dtype=activation_dtype(serve_dtype))
        programs = dict(params_transform=dequantize_tree,
                        spec=self.block_spec)
        if self.block_mode:
            # the one hot program of a block-diffusion model, in the decode
            # step's place
            self._block_step = make_block_step(self.n_heads, self.top_k,
                                               **programs)
        else:
            self._decode = make_decode_step(self.n_heads, self.top_k,
                                            **programs)
        self._prefill = make_prefill_step(self.n_heads, self.top_k,
                                          attn_impl=attn_impl, **programs)
        self._buckets = self._make_buckets(min_bucket)
        # --- serving fast path (ISSUE 16), every seam defaulting off ---
        self.prefill_chunk = (None if prefill_chunk is None
                              else int(prefill_chunk))
        if prefix_cache is True:
            self._prefix = PrefixPageCache(
                page_tokens=prefix_page_tokens,
                capacity_pages=prefix_cache_pages,
                registry=self.registry)
        else:
            self._prefix = prefix_cache or None
        # one chunk executable serves chunked prefill AND the
        # prefix-cache suffix path (compiles keyed by chunk width)
        self._chunk = (make_chunk_prefill_step(self.n_heads, self.top_k,
                                               **programs)
                       if (self.prefill_chunk is not None
                           or self._prefix is not None) else None)
        self._chunking: dict = {}       # slot -> pending chunk plan
        # the environment's speculation switch is for one-token models
        # whose whole state is K/V
        self.spec = (None if self.block_mode or self.ssm_mode
                     else resolve_speculative(speculative))
        if self.spec is not None:
            if self.spec.k + 1 >= max_len:
                raise ValueError(
                    f"speculative k={self.spec.k} needs k+1 < max_len "
                    f"({max_len})")
            draft_raw = (self.spec.draft_params
                         if self.spec.draft_params is not None
                         else draft_truncate_params(params,
                                                    self.spec.draft_layers))
            self._draft_params = prepare_serve_params(draft_raw,
                                                      serve_dtype)
            self._draft_cache = init_kv_cache(
                lm_dims(draft_raw)["n_layers"], self.n_slots,
                n_kv, head_dim, self.max_len,
                dtype=activation_dtype(serve_dtype))
            self._draft_decode = make_decode_step(self.n_heads, self.top_k,
                                                  **programs)
            self._draft_prefill = make_prefill_step(
                self.n_heads, self.top_k, attn_impl=attn_impl, **programs)
            self._verify = make_verify_step(self.n_heads, self.top_k,
                                            **programs)
        self.spec_verify_steps = 0
        self.spec_accepted_total = 0
        self._spec_proposed_total = 0
        # every serve_* instrument of the per-token path is bound once
        # here: the registry's get-or-create under its rlock stays off the
        # tick (the ledger's idle gaps found it there, PR 24). The
        # counter the full-prefix-hit pin asserts against exists (at 0)
        # from construction; spec instruments likewise when armed
        reg = self.registry
        self._c_requests = reg.counter("serve_requests_total")
        self._c_tokens = reg.counter("serve_tokens_total")
        self._c_prefill_dispatches = reg.counter(
            "serve_prefill_dispatches_total")
        self._c_attn_chunks_read = reg.counter(
            "serve_attn_chunks_read_total")
        self._c_attn_chunks = reg.counter("serve_attn_chunks_total")
        self._c_completed: dict = {}    # reason -> counter, born at first use
        self._g_queue_depth = reg.gauge("serve_queue_depth")
        self._g_active_slots = reg.gauge("serve_active_slots")
        self._h_prefill_ms = reg.histogram("serve_prefill_ms")
        self._h_decode_step_ms = reg.histogram("serve_decode_step_ms")
        self._h_request_ms = reg.histogram("serve_request_ms")
        self._h_first_token_ms = reg.histogram("serve_first_token_ms")
        # runtime profiler (ISSUE 17): the scheduler loop phase-times
        # each decode tick into the runprof rings/gauges when armed —
        # instruments pre-created HERE so the first flush's increment
        # is visible to rate windows (the PR 15 discipline; the
        # decode tick carries no xprofile FLOPs, so the "<"-trapped
        # runprof_measured_mfu gauge stays unborn)
        self._runprof = resolve_runprof(runprof)
        if self._runprof is not None and self._runprof._registry is None:
            # an engine on a private registry keeps its profiler there too
            self._runprof._registry = self.registry
        if self._runprof is not None:
            self._runprof.arm("serve_decode")
        # a collection stops the scheduler with the device idle, and no
        # Python frame shows it: each one is a host.gc phase in the ring
        _trace.install_gc_phases()
        if self.spec is not None:
            self._c_spec_verify_steps = reg.counter(
                "serve_spec_verify_steps_total")
            self._c_spec_accepted = reg.counter(
                "serve_spec_accepted_tokens_total")
            self._c_spec_draft_prefills = reg.counter(
                "serve_spec_draft_prefills_total")
            self._c_spec_draft_steps = reg.counter(
                "serve_spec_draft_steps_total")
            self._h_spec_accepted = reg.histogram(
                "serve_spec_accepted_per_verify")
            self._h_verify_step_ms = reg.histogram("serve_verify_step_ms")
            # serve_spec_accept_rate stays UNBORN until the warmup floor
            # of verify steps: the serve_spec_accept_collapse rule
            # (op "<") must read "not yet speculating" as no-data
        if self.block_mode:
            self._c_block_steps = reg.counter("serve_block_steps_total")
            self._c_block_forwards = {
                kind: reg.counter("serve_block_forwards_total",
                                  {"kind": kind})
                for kind in ("denoise", "commit")}
            self._c_block_accepted = reg.counter(
                "serve_block_tokens_accepted_total")
        # the phase attrs of a prefill dispatch: under a layer pattern the
        # state-space layers it seeds
        self._prefill_attrs = {}
        if self.ssm_mode:
            reg.gauge("serve_ssm_state_bytes").set(float(sum(
                self._cache[name].nbytes for name in ("conv", "ssm"))))
            self._c_ssm_writes = reg.counter("serve_ssm_state_writes_total")
            self._prefill_attrs["ssm_layers"] = int(
                self._cache["ssm"].shape[0])
        self._key = jax.random.PRNGKey(seed)
        # the lockwatch seam (ISSUE 11): plain primitives unless the
        # watch is armed (lockwatch fixture / DL4J_TPU_LOCKWATCH=1)
        self._lock = make_rlock("serve.engine")
        self._work = make_condition(self._lock, name="serve.engine")
        self._queue: List[ServeRequest] = []
        self._slots: List[Optional[ServeRequest]] = [None] * self.n_slots
        # host mirrors of the decode step's per-slot inputs
        self._tokens = np.zeros((self.n_slots,), np.int32)
        self._positions = np.zeros((self.n_slots,), np.int32)
        self._temps = np.zeros((self.n_slots,), np.float32)
        # block mode: ``_positions`` holds each slot's current block's first
        # row, and these its B tokens and which of them are still masked.
        # The bitmap is the engine's own record: a prompt may hold the mask
        # token's id as a token
        width = self.block_spec.block_length
        self._block_tokens = np.zeros((self.n_slots, width), np.int32)
        self._block_masked = np.zeros((self.n_slots, width), bool)
        self._rid = itertools.count()
        self._step_idx = 0
        self._tick = None  # the open ``tick`` phase; set under the lock
        self._thread: Optional[threading.Thread] = None
        self._running = False
        # aggregate accounting for stats()/bench
        self.tokens_total = 0
        self.requests_total = 0
        self.decode_steps = 0
        self._occupancy_sum = 0
        self._t_first_activity: Optional[float] = None

    def _check_state_space(self, speculative, prefill_chunk,
                           prefix_cache) -> None:
        """The fast paths that take a slot's state for its K/V pages alone
        are refused under a layer pattern with state-space layers: each
        would need the recurrent state snapshotted or carried across a
        dispatch, which the engine does not do."""
        if self.block_spec.generation != "autoregressive":
            raise ValueError(
                "block-diffusion generation with state-space layers: a "
                "denoising forward rewrites a block's rows, and a recurrent "
                "state cannot take back what a provisional row added")
        if speculative:
            raise ValueError(
                "speculative= with state-space layers: a rejected proposal "
                "has already advanced the recurrent state, and rolling it "
                "back needs a snapshot of every slot's state a position")
        if prefill_chunk is not None:
            raise ValueError(
                "prefill_chunk= with state-space layers: each chunk would "
                "have to start from the state the previous chunk left, and "
                "the chunk step carries K/V pages alone")
        if prefix_cache:
            raise ValueError(
                "prefix_cache= with state-space layers: a cached prefix's "
                "pages hold K/V alone, and the recurrent state at the "
                "prefix's end would have to be stored with them")

    def _check_block_mode(self, speculative, prefill_chunk,
                          prefix_cache) -> None:
        """A block-diffusion spec that cannot be served, and the fast paths
        that assume one causal token a step, are refused at construction."""
        bs = self.block_spec
        if not (1 <= bs.denoising_steps <= bs.block_length) or \
                bs.attn_mask != "block":
            raise ValueError(
                "block-diffusion generation needs attn_mask='block' and 1 <= "
                f"denoising_steps <= block_length, got {bs}")
        if bs.mask_token_id is None or not (
                0 <= bs.mask_token_id < self.dims["vocab"]):
            raise ValueError(
                f"mask_token_id {bs.mask_token_id} is not a row of the "
                f"embedding ({self.dims['vocab']} rows)")
        if speculative:
            raise ValueError(
                "speculative= with a block-diffusion spec: a draft proposes "
                "the next tokens of a causal sequence for a verify step that "
                "accepts a prefix of them; a block-diffusion step has no "
                "next token, it unmasks positions of a block in any order")
        if prefill_chunk is not None:
            raise ValueError(
                "prefill_chunk= with a block-diffusion spec: chunks go "
                "through the cached step a chunk at a time and end in a "
                "sampled first token; this prompt pass stores whole blocks "
                "that see each other across a chunk's edge and samples "
                "nothing")
        if prefix_cache:
            raise ValueError(
                "prefix_cache= with a block-diffusion spec: a full hit "
                "leaves the last prompt token to a decode tick and a partial "
                "one to a causal suffix chunk; a block-diffusion slot has "
                "neither, and its prompt's remainder opens a generated block")

    # ------------------------------------------------------------ loading ----
    @classmethod
    def from_checkpoint(cls, root: str, *, n_heads: Optional[int] = None,
                        step: Optional[int] = None, **kwargs):
        """Build an engine from a sharded LM checkpoint: the manifest
        supplies the template (template-free restore through the
        resharding loader), ``meta["lm"]`` (``lm_checkpoint_meta``) or the
        ``n_heads`` argument supplies the head count the shapes erase."""
        import os

        from deeplearning4j_tpu.scaleout.ckpt import manifest as mf
        from deeplearning4j_tpu.scaleout.ckpt.checkpointer import Checkpointer
        from deeplearning4j_tpu.scaleout.ckpt.reshard import (
            latest_step_dir,
            template_from_manifest,
        )

        if step is None:
            step_dir = latest_step_dir(root)
            if step_dir is None:
                raise FileNotFoundError(
                    f"no committed checkpoint under {root}")
        else:
            step_dir = os.path.join(root, mf.step_dir_name(step))
        manifest = mf.read_manifest(step_dir)
        template = template_from_manifest(manifest)
        state, _step, meta = Checkpointer(root).restore(
            template, step=manifest.step)
        # training saves wrap the tree as {"params": ...}; unwrap either way
        params = state.get("params", state) if isinstance(state, dict) \
            else state
        if not (isinstance(params, dict) and "embed" in params
                and "blocks" in params):
            raise ValueError(
                f"checkpoint under {root} is not a flagship-LM params tree "
                "(no embed/blocks leaves) — the decode engine serves "
                "models/transformer_lm checkpoints only")
        lm_meta = (meta or {}).get("lm") or {}
        n_heads = n_heads if n_heads is not None else lm_meta.get("n_heads")
        if n_heads is None:
            raise ValueError(
                "n_heads is not recoverable from param shapes — save with "
                "meta=lm_checkpoint_meta(params, n_heads) or pass n_heads=")
        kwargs.setdefault("top_k", int(lm_meta.get("top_k", 2)))
        kwargs.setdefault("spec", spec_from_meta(lm_meta))
        kwargs.setdefault("weight_version", f"ckpt-step-{manifest.step}")
        return cls(params, int(n_heads), **kwargs)

    @classmethod
    def from_live_params(cls, params, n_heads: int, *, device=None,
                         **kwargs):
        """Any-mesh cold start from a params tree ALREADY resident on
        devices (ISSUE 14) — e.g. a live trainer's sharded flagship tree:
        every leaf is moved onto the serving device through the in-graph
        redistribution plans (``scaleout.ckpt.redistribution``), so the
        adoption is device-to-device collectives, never a host gather of
        sharded state. Disk checkpoints keep the host-assembly path
        (``from_checkpoint``). ``device`` defaults to the first local
        device; the resulting engine is token-identical to one built from
        the same params via the host path (tests/test_redistribution.py).
        """
        from jax.sharding import SingleDeviceSharding

        from deeplearning4j_tpu.scaleout.ckpt.redistribution import (
            redistribute_tree,
        )

        dev = device if device is not None else jax.devices()[0]
        dst = jax.tree_util.tree_map(
            lambda _: SingleDeviceSharding(dev), params)
        kwargs.setdefault("weight_version", "live-params")
        return cls(redistribute_tree(params, dst), int(n_heads), **kwargs)

    # ---------------------------------------------------------- admission ----
    def _make_buckets(self, min_bucket: int) -> List[int]:
        buckets, b = [], max(2, int(min_bucket))
        while b < self.max_len:
            buckets.append(b)
            b *= 2
        buckets.append(self.max_len)
        return buckets

    def bucket_for(self, n: int) -> int:
        for b in self._buckets:
            if b >= n:
                return b
        return self.max_len

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 16,
               temperature: float = 0.0,
               eos_id=_UNSET) -> ServeRequest:
        """Enqueue a request (admitted into a slot by a later ``step``).
        ``temperature <= 0`` is greedy; ``eos_id`` defaults to the
        engine's (None = never)."""
        prompt = [int(t) for t in prompt]
        vocab = self.dims["vocab"]
        if not prompt:
            raise ValueError("empty prompt")
        if any(t < 0 or t >= vocab for t in prompt):
            raise ValueError(f"prompt tokens must be in [0, {vocab})")
        if len(prompt) > self.max_len - 1:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds max_len-1 = "
                f"{self.max_len - 1} (one cache position must remain for "
                "generation)")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        width = self.block_spec.block_length
        if self.block_mode and \
                len(prompt) // width * width + width > self.max_len:
            raise ValueError(
                f"prompt length {len(prompt)} leaves no whole block of "
                f"{width} below max_len = {self.max_len}")
        req = ServeRequest(next(self._rid), prompt, max_new_tokens,
                           temperature,
                           self.eos_id if eos_id is _UNSET else eos_id)
        req.t_submit = time.perf_counter()
        tracer = _trace.get_tracer()
        if tracer is not None:
            # parents under the submitting thread's current span (the
            # UiServer http.request span / a loadgen span), or roots a
            # fresh trace; children below parent under it EXPLICITLY
            # because they run on the scheduler thread
            req.span = tracer.start_span(
                "serve.request",
                attrs={"rid": req.rid, "prompt_len": len(prompt),
                       "max_new_tokens": req.max_new_tokens,
                       "temperature": req.temperature,
                       "weight_version": self.weight_version})
            req.queue_span = tracer.start_span("serve.queue_wait",
                                               parent=req.span)
            req.trace_id = req.span.trace_id
        with self._work:
            self._queue.append(req)
            self.requests_total += 1
            if self._t_first_activity is None:
                self._t_first_activity = req.t_submit
            self._c_requests.inc()
            self._g_queue_depth.set(float(len(self._queue)))
            self._work.notify_all()
        return req

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._slots) if r is None]

    def _admit(self, req: ServeRequest, slot: int) -> None:
        n = len(req.prompt)
        if req.queue_span is not None:
            req.queue_span.end()
            req.queue_span = None
        req.t_admit = time.perf_counter()
        req.slot = slot
        self._slots[slot] = req
        self._temps[slot] = req.temperature
        # ---- prefix-cache lookup + slot seed (zero flagship compute) ----
        plen = 0
        if self._prefix is not None:
            t0 = time.perf_counter()
            plen, k_pages, v_pages = self._prefix.lookup(req.prompt)
            if plen:
                kcat = (k_pages[0] if len(k_pages) == 1
                        else jnp.concatenate(k_pages, axis=2))
                vcat = (v_pages[0] if len(v_pages) == 1
                        else jnp.concatenate(v_pages, axis=2))
                ck, cv = seed_slot_pages(self._cache["k"],
                                         self._cache["v"], kcat, vcat,
                                         np.int32(slot))
                self._cache = {"k": ck, "v": cv}
                req.prefill_cached_ms = (time.perf_counter() - t0) * 1000.0  # graftlint: allow[untimed-dispatch] attribution stamp, not a benchmark — syncing here would stall the scheduler hot path; the seed's cost is fenced by the decode step that consumes the cache
                req.prefill_ms += req.prefill_cached_ms
            req.cached_tokens = plen
        req.prefill_span = (req.span.tracer.start_span(
            "serve.prefill", parent=req.span,
            attrs={"slot": slot, "prompt_len": n, "cached_tokens": plen})
            if req.span is not None else None)
        if self.spec is not None:
            self._draft_admit(req, slot, n)
        # ---- full hit: the cached prefix covers every position the last
        # prompt token's decode tick doesn't write itself — NO flagship
        # prefill dispatch; the first token arrives from the shared
        # decode step, exactly as if prefill had just run. Not with
        # state-space layers: only a prefill overwrites the slot's state,
        # so a one-token prompt is prefilled too ----
        if plen >= n - 1 and not self.ssm_mode:
            self._tokens[slot] = req.prompt[-1]
            self._positions[slot] = n - 1
            self._finish_prefill_span(req, mode="cached_full")
            if req.span is not None:
                req.decode_span = req.span.tracer.start_span(
                    "serve.decode", parent=req.span, attrs={"slot": slot})
            return
        # ---- chunked path: long prompts (or any cached-prefix suffix)
        # run through the chunk executable; interleaved one chunk per
        # scheduler iteration when prefill_chunk is configured ----
        if self._chunk is not None and (
                plen > 0 or (self.prefill_chunk is not None
                             and n > self.prefill_chunk)):
            plan = self._chunk_plan(req, plen)
            if self.prefill_chunk is not None and len(plan) > 1:
                # garbage-write shield: the shared decode tick writes this
                # slot at _positions — point it where the next chunk will
                # overwrite before any query can read it
                self._positions[slot] = plan[0][1]
                self._chunking[slot] = {"req": req, "plan": plan,
                                        "idx": 0}
                return
            for idx in range(len(plan)):
                self._run_chunk(req, slot, plan, idx)
            return
        # ---- classic one-shot bucketed prefill ----
        if not self.block_mode:
            tok, t1 = self._prefill_prompt(req, slot)
            self._complete_prefill(req, slot, tok, t1, mode="full")
            return
        # ---- block diffusion: the prompt's whole blocks are stored (its
        # remainder's rows too, which its block's first forward overwrites
        # before it reads them; a prompt shorter than a block stores
        # nothing); the remainder sits, known, at the head of the first
        # generated block ----
        width = self.block_spec.block_length
        stored = n // width * width
        if stored:
            self._prefill_prompt(req, slot)
            self._h_prefill_ms.observe(req.prefill_ms, exemplar=req.trace_id)
        self._finish_prefill_span(req, mode="blocks")
        self._positions[slot] = stored
        self._block_tokens[slot] = 0
        self._block_tokens[slot, :n - stored] = req.prompt[stored:]
        self._block_masked[slot] = True
        self._block_masked[slot, :n - stored] = False
        if req.span is not None:
            req.decode_span = req.span.tracer.start_span(
                "serve.decode", parent=req.span, attrs={"slot": slot})

    def _prefill_prompt(self, req: ServeRequest, slot: int) -> tuple:
        """One dispatch of the prompt, padded to its bucket, into ``slot``'s
        page, fenced: (the token it sampled, the fence's stamp)."""
        n = len(req.prompt)
        bucket = self.bucket_for(n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = req.prompt
        if req.prefill_span is not None:
            req.prefill_span.set_attr("bucket", bucket)
        with _trace.phase("tick.prefill", self._tick.tick, rid=req.rid,
                          prompt_len=n, bucket=bucket,
                          **self._prefill_attrs) as ph:
            with _trace.phase("tick.dispatch", self._tick.tick,
                              program="jit_prefill"):
                self._cache, tok = self._prefill(
                    self.params, self._cache, padded, n - 1, slot,
                    np.float32(req.temperature), self._key, self._step_idx)
            self._step_idx += 1
            self._c_prefill_dispatches.inc()
            if self.ssm_mode:  # the slot's state overwritten whole
                self._c_ssm_writes.inc()
            with _trace.phase("tick.fence", self._tick.tick,
                              program="jit_prefill"):
                tok = int(np.asarray(tok))  # graftlint: allow[blocking-under-lock] deliberate: the scheduler lock IS the serialization — slot state may only change together with the fenced prefill result
        req.prefill_suffix_ms += ph.ms
        req.prefill_ms += ph.ms
        return tok, ph.t1

    def _draft_admit(self, req: ServeRequest, slot: int, n: int) -> None:
        """Seed the DRAFT cache for an admitted slot (speculative only):
        one draft-prefill dispatch over the full prompt. Counted apart
        from ``serve_prefill_dispatches_total`` — the full-hit pin is
        about flagship work; the draft is the cost of speculation."""
        bucket = self.bucket_for(n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = req.prompt
        self._draft_cache, _ = self._draft_prefill(
            self._draft_params, self._draft_cache, padded, n - 1, slot,
            np.float32(0.0), self._key, self._step_idx)
        self._step_idx += 1
        self._c_spec_draft_prefills.inc()

    def _chunk_plan(self, req: ServeRequest, plen: int) -> list:
        """Chunk schedule covering prompt positions [plen, n): a list of
        ``(tokens (1, W) np.int32, start, last_idx)``. Interleaved mode
        (prefill_chunk set, suffix > chunk) uses W = prefill_chunk with
        the FINAL chunk shifted left to ``n - W`` (same shape, overlap
        rewrites identical values); the prefix-suffix one-shot uses one
        bucket-width chunk. Every start satisfies start + W <= max_len,
        so the in-graph dynamic write can never clamp onto live
        positions."""
        n = len(req.prompt)
        C = self.prefill_chunk
        if C is not None and n - plen > C:
            starts = list(range(plen, n - C, C))
            starts.append(n - C)
            width = C
        else:
            width = min(self.bucket_for(n - plen), self.max_len)
            starts = [max(0, n - width)]
        plan = []
        for i, s in enumerate(starts):
            toks = np.zeros((1, width), np.int32)
            real = req.prompt[s:min(s + width, n)]
            toks[0, :len(real)] = real
            last_idx = (n - 1 - s) if i == len(starts) - 1 else width - 1
            plan.append((toks, s, last_idx))
        return plan

    def _run_chunk(self, req: ServeRequest, slot: int, plan: list,
                   idx: int) -> None:
        """Dispatch chunk ``idx``; on the final chunk, complete the
        admission with its sampled first token."""
        toks, start, last_idx = plan[idx]
        final = idx == len(plan) - 1
        with _trace.phase("tick.prefill", self._tick.tick, rid=req.rid,
                          prompt_len=len(req.prompt),
                          bucket=toks.shape[1]) as ph:
            with _trace.phase("tick.dispatch", self._tick.tick,
                              program="jit_chunk"):
                self._cache, tok = self._chunk(
                    self.params, self._cache, toks, np.int32(start),
                    np.int32(last_idx), np.int32(slot),
                    np.float32(req.temperature), self._key, self._step_idx)
            self._step_idx += 1
            self._c_prefill_dispatches.inc()
            req.prefill_chunks += 1
            if final:  # a chunk before it is left to run: no fence
                with _trace.phase("tick.fence", self._tick.tick,
                                  program="jit_chunk"):
                    tok = int(np.asarray(tok))  # graftlint: allow[blocking-under-lock] deliberate: same fencing contract as the classic prefill — slot state changes only with the fenced result
        req.prefill_suffix_ms += ph.ms
        req.prefill_ms += ph.ms
        if final:
            self._chunking.pop(slot, None)
            self._complete_prefill(
                req, slot, tok, ph.t1,
                mode="suffix" if req.cached_tokens else "chunked")
        else:
            # shield: next chunk overwrites [next_start, next_start + W)
            self._positions[slot] = plan[idx + 1][1]

    def _complete_prefill(self, req: ServeRequest, slot: int, tok: int,
                          now: float, mode: str) -> None:
        """Prompt K/V fully resident: publish pages to the prefix cache,
        arm decode state, accept the first token."""
        if self._prefix is not None:
            n_pages = len(req.prompt) // self._prefix.page_tokens
            if n_pages:
                span = n_pages * self._prefix.page_tokens
                self._prefix.insert(
                    req.prompt,
                    self._cache["k"][:, slot, :, :span, :],
                    self._cache["v"][:, slot, :, :span, :])
        self._h_prefill_ms.observe(req.prefill_ms, exemplar=req.trace_id)
        self._finish_prefill_span(req, mode=mode)
        self._positions[slot] = len(req.prompt)
        if req.span is not None:
            # started BEFORE the first accept: max_new_tokens=1 / instant
            # EOS retire the request inside this very call
            req.decode_span = req.span.tracer.start_span(
                "serve.decode", parent=req.span, attrs={"slot": slot})
        self._accept_tokens(req, (tok,), now)

    def _finish_prefill_span(self, req: ServeRequest, mode: str) -> None:
        if req.prefill_span is None:
            return
        req.prefill_span.set_attr("mode", mode)
        req.prefill_span.set_attr("cached_tokens", req.cached_tokens)
        req.prefill_span.set_attr("chunks", req.prefill_chunks)
        req.prefill_span.set_attr("cached_ms",
                                  round(req.prefill_cached_ms, 3))
        req.prefill_span.set_attr("suffix_ms",
                                  round(req.prefill_suffix_ms, 3))
        req.prefill_span.end()
        req.prefill_span = None

    def _accept_tokens(self, req: ServeRequest, toks: Sequence[int],
                       now: float) -> None:
        """Record the tokens a step gives ``req``, in position order with
        the step's one stamp (one in the one-token tick, a whole block of
        0 to B in block-diffusion generation), and retire it at EOS /
        max_new_tokens / cache exhaustion (iteration-level eviction); what
        follows the token that retires it is dropped."""
        if req.t_first is None and toks:
            # stamped at the first accepted token — for the prefix-cache
            # full-hit path that is the shared decode tick, not a prefill
            req.t_first = now
        for tok in toks:
            if req.eos_id is not None and tok == req.eos_id:
                self._finish(req, "eos", now)
                return
            req.generated.append(tok)
            req.t_tokens.append(now)
            if req.decode_span is not None:
                req.decode_span.add_event("accept", token=tok,
                                          n=len(req.generated))
            self.tokens_total += 1
            self._c_tokens.inc()
            if len(req.generated) >= req.max_new_tokens:
                self._finish(req, "max_new_tokens", now)
                return
            if int(self._positions[req.slot]) >= self.max_len:
                # the cache page is exhausted: this token was the last that
                # fits
                self._finish(req, "max_len", now)
                return
            self._tokens[req.slot] = tok

    def _finish(self, req: ServeRequest, reason: str, now: float) -> None:
        req.finish_reason = reason
        req.t_done = now
        if req.span is not None:
            if req.decode_span is not None:
                req.decode_span.set_attr("decode_ms",
                                         round(req.decode_ms, 3))
                req.decode_span.set_attr("tokens", len(req.generated))
                req.decode_span.end()
                req.decode_span = None
            retire = req.span.tracer.start_span(
                "serve.retire", parent=req.span,
                attrs={"reason": reason, "tokens": len(req.generated),
                       "weight_version": self.weight_version})
            retire.end()
            # the latency-attribution attrs tools/trace_report.py tables:
            # queue_wait + prefill + decode + gap ≡ latency by construction
            # (gap = scheduler time the request sat admitted but outside
            # its own prefill/decode dispatches)
            queue_ms = ((req.t_admit or now) - req.t_submit) * 1000.0
            latency_ms = (now - req.t_submit) * 1000.0
            req.span.set_attr("queue_wait_ms", round(queue_ms, 3))
            req.span.set_attr("prefill_ms", round(req.prefill_ms, 3))
            # fast-path split (ISSUE 16): prefill_ms = cached-skip (page
            # seed) + suffix-prefill (chunk/classic compute) — what
            # tools/trace_report.py's serve attribution tables
            req.span.set_attr("prefill_cached_ms",
                              round(req.prefill_cached_ms, 3))
            req.span.set_attr("prefill_suffix_ms",
                              round(req.prefill_suffix_ms, 3))
            req.span.set_attr("cached_tokens", req.cached_tokens)
            req.span.set_attr("decode_ms", round(req.decode_ms, 3))
            req.span.set_attr("gap_ms", round(
                latency_ms - queue_ms - req.prefill_ms - req.decode_ms, 3))
            req.span.set_attr("latency_ms", round(latency_ms, 3))
            req.span.set_attr("tokens", len(req.generated))
            req.span.set_attr("finish_reason", reason)
            req.span.end()
            req.span = None
        if req.slot is not None:
            self._slots[req.slot] = None
            self._tokens[req.slot] = 0
            self._positions[req.slot] = 0
            self._temps[req.slot] = 0.0
            self._block_tokens[req.slot] = 0
            self._block_masked[req.slot] = False
            req.slot = None
        completed = self._c_completed.get(reason)
        if completed is None:
            completed = self._c_completed[reason] = self.registry.counter(
                "serve_completed_total", {"reason": reason})
        completed.inc()
        # trace exemplars (ISSUE 15): the request's trace id rides its
        # latency observation into the bucket, so /metrics (OpenMetrics
        # exemplar syntax) and a firing serve_latency_slo_burn alert can
        # name the exact offending traces (None when tracing is off)
        self._h_request_ms.observe((now - req.t_submit) * 1000.0,
                                   exemplar=req.trace_id)
        if req.t_first is not None:
            self._h_first_token_ms.observe(
                (req.t_first - req.t_submit) * 1000.0,
                exemplar=req.trace_id)
        req.done.set()

    # ------------------------------------------------------------- stepping ----
    def has_work(self) -> bool:
        with self._lock:
            return bool(self._queue) or any(
                r is not None for r in self._slots)

    def step(self) -> int:
        """One scheduler iteration: admit into free slots, then one fused
        decode step over every slot. Returns tokens emitted (0 = idle).

        The iteration is one ``tick`` phase (telemetry/trace.py) with its
        children ``tick.admit`` > ``tick.prefill``, ``tick.decode`` and
        ``tick.accept``; each fenced dispatch holds a ``tick.dispatch``
        (the jitted call) and a ``tick.fence`` (the wait for its result).
        That record is the tick's only clock: the decode histogram, the
        ``engine.step`` span and the runprof timing below all read it."""
        tracer = _trace.get_tracer()
        step_span = (tracer.start_span("engine.step", parent=False)
                     if tracer is not None else None)
        decode = None       # the tick.decode phase, where a step ran
        dispatch = None     # its tick.dispatch, where it has one
        decode_ms = 0.0
        with _trace.phase("tick") as tick, self._lock:
            self._tick = tick
            tokens_before = self.tokens_total
            dispatches = self._c_prefill_dispatches.value
            with _trace.phase("tick.admit", tick.tick) as admit:
                free = self._free_slots()
                admitted = 0
                while self._queue and free:
                    req = self._queue.pop(0)
                    self._admit(req, free.pop(0))
                    admitted += 1
                # ---- chunked prefill: ONE chunk per mid-prefill slot per
                # iteration, so a long admission interleaves with decode
                # ticks instead of head-of-line-blocking them ----
                for slot in list(self._chunking):
                    st = self._chunking[slot]
                    self._run_chunk(st["req"], slot, st["plan"], st["idx"])
                    if slot in self._chunking:
                        st["idx"] += 1
                admit.attrs["admitted"] = admitted
                admit.attrs["prefill_dispatches"] = int(
                    self._c_prefill_dispatches.value - dispatches)
            self._g_queue_depth.set(float(len(self._queue)))
            active = [r for r in self._slots
                      if r is not None and r.slot not in self._chunking]
            self._g_active_slots.set(float(len(active)))
            # ---- speculative eligibility: the verify dispatch writes
            # k+1 positions per slot; near the page end (or while a slot
            # is mid-chunk-prefill) fall back to the plain decode tick —
            # dynamic_update_slice clamps out-of-range starts, which
            # would silently overwrite live earlier positions ----
            spec_tick = (
                bool(active) and self.spec is not None
                and not self._chunking
                and all(int(self._positions[r.slot]) + self.spec.k + 1
                        <= self.max_len for r in active))
            if spec_tick:
                with _trace.phase("tick.decode", tick.tick,
                                  occupancy=len(active),
                                  speculative=True) as decode:
                    # k+1 draft dispatches interleaved with their fences
                    # and the acceptance: no finer split (no benchmark
                    # cell turns speculation on)
                    decode_ms = self._spec_step(active, step_span)
            elif active and self.block_mode:
                decode, dispatch = self._block_tick(active, tick)
                decode_ms = decode.ms
            elif active:
                chunks = self._count_attn_chunks(self._positions)
                with _trace.phase("tick.decode", tick.tick,
                                  occupancy=len(active),
                                  chunks=chunks) as decode:
                    with _trace.phase("tick.dispatch", tick.tick,
                                      program="jit_step") as dispatch:
                        self._cache, toks = self._decode(
                            self.params, self._cache, self._tokens,
                            self._positions, self._temps, self._key,
                            self._step_idx)
                    self._step_idx += 1
                    # enqueue back; the device runs until the fence
                    with _trace.phase("tick.fence", tick.tick,
                                      program="jit_step"):
                        toks = np.asarray(toks)  # graftlint: allow[blocking-under-lock] deliberate: retirement must see the fenced decode tokens; submit() blocks here only between decode steps
                decode_ms = decode.ms
                self._h_decode_step_ms.observe(decode_ms)
                self.decode_steps += 1
                self._occupancy_sum += len(active)
                with _trace.phase("tick.accept", tick.tick):
                    for req in active:
                        slot = req.slot
                        if req.decode_span is not None:
                            req.decode_ms += decode_ms
                        self._positions[slot] += 1
                        self._accept_tokens(req, (int(toks[slot]),),
                                            decode.t1)
            occupancy_after = sum(r is not None for r in self._slots)
            if active:
                self._g_active_slots.set(float(occupancy_after))
            tick.attrs.update(
                admitted=admitted, occupancy=len(active),
                retired=len(active) - occupancy_after if active else 0,
                queue_depth=len(self._queue))
            if decode is None:
                tick.attrs["idle"] = not self._chunking
            emitted = self.tokens_total - tokens_before
        if step_span is not None:
            for key, value in tick.attrs.items():
                step_span.set_attr(
                    "admissions" if key == "admitted" else key, value)
            if decode is not None:
                step_span.set_attr("decode_ms", round(decode_ms, 3))
            step_span.end()
        if self._runprof is not None and decode is not None:
            # a speculative tick has no clean dispatch/device split: the
            # whole measured wall goes to the device phase. The host phase
            # is the tick's scheduler work (admission, chunked prefill,
            # retirement): everything outside the decode dispatch + fence
            dispatch_ms = dispatch.ms if dispatch is not None else 0.0
            self._runprof.record(StepTiming(
                label="serve_decode", t_unix=time.time(),
                wall_ms=decode_ms,
                host_ms=max(0.0, tick.ms - decode_ms),
                dispatch_ms=dispatch_ms,
                device_ms=decode_ms - dispatch_ms,
                trace_id=(step_span.trace_id
                          if step_span is not None else None)))
        return emitted

    def _count_attn_chunks(self, positions: np.ndarray) -> int:
        """Counts the cache chunks a decode dispatch at ``positions`` reads
        and the chunks there are; returns the read count."""
        read, total = attn_chunk_reads(int(positions.max()), self.max_len)
        self._c_attn_chunks_read.inc(read)
        self._c_attn_chunks.inc(total)
        return read

    def _block_tick(self, active: List[ServeRequest], tick):
        """The block-diffusion tick (called under the scheduler lock): ONE
        ``jit_block_step`` over every slot inside ``tick.decode``, one
        fence, then ``tick.accept``. A live slot's forward is a denoising
        one while any position of its block is masked, else the commit.
        Returns the ``tick.decode`` phase and its ``tick.dispatch``."""
        width = self.block_spec.block_length
        before = self._block_masked  # the step hands back new arrays
        slots = [r.slot for r in active]
        denoising = before[slots].any(axis=1)
        n_denoise = int(denoising.sum())
        with _trace.phase("tick.decode", tick.tick, occupancy=len(active),
                          kind="block", denoise=n_denoise,
                          commit=len(active) - n_denoise) as decode:
            with _trace.phase("tick.dispatch", tick.tick,
                              program="jit_block_step") as dispatch:
                self._cache, toks, masked = self._block_step(
                    self.params, self._cache, self._block_tokens,
                    self._positions, self._block_masked, self._temps,
                    self._key, self._step_idx)
            self._step_idx += 1
            # enqueue back; the device runs until the fence
            with _trace.phase("tick.fence", tick.tick,
                              program="jit_block_step"):
                toks, masked = jax.device_get((toks, masked))  # graftlint: allow[blocking-under-lock] deliberate: acceptance must see the fenced step's tokens and bitmap, exactly like the decode tick
            # the host's own copies: the next block's state is written here
            toks, masked = toks.copy(), masked.copy()
            decode.attrs["accepted"] = int(
                (before[slots] & ~masked[slots]).sum())
        self._block_tokens, self._block_masked = toks, masked
        self._h_decode_step_ms.observe(decode.ms)
        self.decode_steps += 1
        self._occupancy_sum += len(active)
        self._c_block_steps.inc()
        self._c_block_forwards["denoise"].inc(n_denoise)
        self._c_block_forwards["commit"].inc(len(active) - n_denoise)
        self._c_block_accepted.inc(decode.attrs["accepted"])
        now = decode.t1
        with _trace.phase("tick.accept", tick.tick):
            # plain lists, made once a tick: the records are Python's
            starts = self._positions.tolist()
            was, left, held = before.tolist(), masked.tolist(), toks.tolist()
            for req, slot, denoise in zip(active, slots, denoising.tolist()):
                if req.decode_span is not None:
                    req.decode_ms += decode.ms
                start = starts[slot]
                req.forwards.append((
                    now, start // width, "denoise" if denoise else "commit",
                    tuple(was[slot]),
                    tuple(i for i in range(width)
                          if was[slot][i] and not left[slot][i]),
                    tuple(held[slot])))
                if denoise:
                    if not any(left[slot]):
                        # the block is whole: the request gets what of it
                        # lies past its prompt
                        first = max(0, len(req.prompt) - start)
                        self._accept_tokens(req, held[slot][first:], now)
                elif start + 2 * width > self.max_len:
                    # no room for another block: the page is exhausted
                    self._finish(req, "max_len", now)
                else:
                    self._positions[slot] = start + width
                    toks[slot], masked[slot] = 0, True
        return decode, dispatch

    def _spec_step(self, active: List[ServeRequest], step_span) -> float:
        """One speculative iteration (called under the scheduler lock):
        k draft decode dispatches propose, ONE flagship verify dispatch
        of width k+1 checks, the host accepts the longest matching
        prefix + the flagship's bonus token per slot. Greedy slots emit
        1..k+1 tokens per flagship dispatch and the stream is exactly
        the non-speculative one; sampling slots accept only position 0's
        sampled token (distribution-correct, no speedup)."""
        k = self.spec.k
        t0 = time.perf_counter()
        drafts = np.zeros((self.n_slots, k), np.int32)
        cur = self._tokens.copy()
        dpos = self._positions.copy()
        # k+1 dispatches, not k: the extra one writes the LAST proposal's
        # K/V into the draft cache, so a fully-accepted round leaves no
        # hole at position p+k when the next round starts from p+k+1
        # (the final dispatch's proposal is discarded). Eligibility
        # (p + k + 1 <= max_len) bounds every write.
        for j in range(k + 1):
            self._count_attn_chunks(dpos)
            self._draft_cache, dt = self._draft_decode(
                self._draft_params, self._draft_cache, cur, dpos,
                self._temps, self._key, self._step_idx)
            self._step_idx += 1
            if j < k:
                dt = np.asarray(dt)  # graftlint: allow[blocking-under-lock] deliberate: proposal j+1 feeds on proposal j; the scheduler lock is the serialization
                drafts[:, j] = dt
                cur = dt.copy()
            # a new array: the last dispatch is not fenced, and on the CPU
            # it may still be reading the one it was handed
            dpos = dpos + 1
        self._c_spec_draft_steps.inc(k + 1)
        t1 = time.perf_counter()
        vt = np.concatenate([self._tokens[:, None], drafts], axis=1)
        self._cache, vtoks = self._verify(
            self.params, self._cache, vt, self._positions, self._temps,
            self._key, self._step_idx)
        self._step_idx += 1
        vtoks = np.asarray(vtoks)  # graftlint: allow[blocking-under-lock] deliberate: acceptance must see the fenced verify tokens, exactly like the decode tick
        now = time.perf_counter()
        draft_ms = (t1 - t0) * 1000.0
        verify_ms = (now - t1) * 1000.0
        # trace exemplar on the verify latency observation (ISSUE 16):
        # a slow verify is attributable to a real request's trace
        self._h_verify_step_ms.observe(verify_ms,
                                       exemplar=active[0].trace_id)
        self._h_decode_step_ms.observe(draft_ms + verify_ms)
        self._c_spec_verify_steps.inc()
        self.spec_verify_steps += 1
        self.decode_steps += 1
        self._occupancy_sum += len(active)
        for req in active:
            slot = req.slot
            p = int(self._positions[slot])
            if req.temperature > 0:
                a, emitted = 0, [int(vtoks[slot, 0])]
            else:
                a, emitted = accept_longest_prefix(drafts[slot],
                                                   vtoks[slot])
            self.spec_accepted_total += a
            self._spec_proposed_total += k
            self._c_spec_accepted.inc(a)
            self._h_spec_accepted.observe(float(a), exemplar=req.trace_id)
            if req.decode_span is not None:
                req.decode_ms += draft_ms + verify_ms
                req.decode_span.add_event("verify", accepted=a,
                                          proposed=k,
                                          emitted=len(emitted))
            for j, tok in enumerate(emitted):
                # a token at a time: the page-exhaustion rule reads the
                # position each one leaves
                self._positions[slot] = p + j + 1
                self._accept_tokens(req, (tok,), now)
                if req.done.is_set():
                    break  # retired mid-run; trailing tokens discarded
        if self.spec_verify_steps >= 8:
            self.registry.gauge("serve_spec_accept_rate").set(
                self.spec_accepted_total
                / max(1, self._spec_proposed_total))
        if step_span is not None:
            step_span.set_attr("speculative", True)
            step_span.set_attr("draft_ms", round(draft_ms, 3))
        return draft_ms + verify_ms

    def run_until_idle(self, max_steps: int = 100_000) -> int:
        """Drive ``step`` until queue and slots drain; returns tokens."""
        total = 0
        for _ in range(max_steps):
            if not self.has_work():
                return total
            total += self.step()
        raise RuntimeError(f"engine still busy after {max_steps} steps")

    # ------------------------------------------------------- request API ----
    def generate(self, prompt: Sequence[int], max_new_tokens: int = 16,
                 temperature: float = 0.0, eos_id=_UNSET,
                 timeout: Optional[float] = None) -> List[int]:
        """Blocking convenience: submit + wait (background loop running)
        or submit + drive inline. Returns the generated tokens."""
        req = self.submit(prompt, max_new_tokens=max_new_tokens,
                          temperature=temperature, eos_id=eos_id)
        if self._thread is None:
            deadline = None if timeout is None else \
                time.perf_counter() + timeout
            while not req.done.is_set():
                self.step()
                if deadline is not None and time.perf_counter() > deadline:
                    raise TimeoutError(f"request {req.rid} timed out")
        elif not req.done.wait(timeout):
            raise TimeoutError(f"request {req.rid} timed out")
        return list(req.generated)

    # --------------------------------------------------- background loop ----
    def start(self) -> None:
        """Run the scheduler on a daemon thread (the UiServer deployment
        shape: handler threads submit, one loop decodes)."""
        with self._lock:
            if self._thread is not None:
                return
            self._running = True
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

    def _loop(self) -> None:
        while True:
            with self._work:
                while self._running and not (
                        self._queue or any(r is not None
                                           for r in self._slots)):
                    self._work.wait(0.05)
                if not self._running:
                    return
            self.step()

    def stop(self) -> None:
        # swap the handle under the lock (two concurrent stop()s must not
        # both join-then-None it; generate() reads _thread unlocked), join
        # outside it — the loop needs the lock to observe _running
        with self._work:
            self._running = False
            self._work.notify_all()
            thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=10)

    # -------------------------------------------------------------- stats ----
    def stats(self) -> dict:
        """The ``/api/serve`` snapshot: scheduler state + throughput +
        per-in-flight-request ages (ISSUE 12 satellite — a stuck request
        is visible from the UI as a growing ``queued_s``/``running_s``,
        not only as a hung client)."""
        with self._lock:
            now = time.perf_counter()
            in_flight = []
            for r in self._queue:
                in_flight.append({
                    "rid": r.rid, "state": "queued",
                    "queued_s": round(now - r.t_submit, 3),
                    "tokens": 0, "prompt_len": len(r.prompt)})
            for r in self._slots:
                if r is None:
                    continue
                in_flight.append({
                    "rid": r.rid, "state": "running", "slot": r.slot,
                    "queued_s": round(
                        ((r.t_admit or now) - r.t_submit), 3),
                    "running_s": round(now - (r.t_admit or now), 3),
                    "tokens": len(r.generated),
                    "prompt_len": len(r.prompt)})
            active = sum(r is not None for r in self._slots)
            elapsed = (now - self._t_first_activity
                       if self._t_first_activity is not None else 0.0)
            return {
                "slots": self.n_slots,
                "active_slots": active,
                "queue_depth": len(self._queue),
                "max_len": self.max_len,
                "serve_dtype": self.serve_dtype or "f32",
                "weight_bytes": self.weight_bytes,
                "weight_version": self.weight_version,
                "prefill_buckets": list(self._buckets),
                "requests_total": self.requests_total,
                "tokens_total": self.tokens_total,
                "decode_steps": self.decode_steps,
                "occupancy_mean": (self._occupancy_sum / self.decode_steps
                                   if self.decode_steps else 0.0),
                "tokens_per_sec": (self.tokens_total / elapsed
                                   if elapsed > 0 else 0.0),
                "in_flight": in_flight,
                "prefill_chunk": self.prefill_chunk,
                "chunking_slots": len(self._chunking),
                "prefix_cache": (self._prefix.stats()
                                 if self._prefix is not None else None),
                "speculative": ({
                    "k": self.spec.k,
                    "verify_steps": self.spec_verify_steps,
                    "accepted_tokens": self.spec_accepted_total,
                    "accept_rate": (
                        self.spec_accepted_total
                        / max(1, self._spec_proposed_total)),
                } if self.spec is not None else None),
                "model": dict(self.dims, n_heads=self.n_heads,
                              top_k=self.top_k,
                              generation=self.block_spec.generation),
            }

    def metrics_record(self) -> dict:
        """Every ``serve_*`` instrument in this engine's registry as a
        flat step-log-ready dict (labeled counters summed, histograms as
        ``_count``/``_sum``) — the block ``summarize_step_log`` and
        ``tools/telemetry_report.py`` render, mirroring
        ``lockwatch.metrics_record()`` (pinned by the ISSUE 12 meta-test:
        a serve metric that exists in the registry cannot ship
        unrendered)."""
        from deeplearning4j_tpu.telemetry.registry import flat_record

        return flat_record(self.registry, prefixes=("serve_",))
