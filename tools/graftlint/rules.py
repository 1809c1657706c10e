"""The JAX-aware rule set.

Each rule targets a failure class the reviews keep re-finding (see
tools/graftlint/__init__.py). Rules are deliberately *in-file* analyses:
cross-module call graphs would need imports (slow, fragile in a lint
gate); the idioms these rules police — jitted step definitions, timed
bench loops, PRNG threading — are local by construction in this tree.
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from tools.graftlint.engine import (
    Finding,
    ModuleContext,
    dotted,
    last_part,
    register,
)

_SYNC_LAST = {"block_until_ready", "device_get", "item", "tolist"}
_NP_PREFIXES = ("np.", "numpy.", "onp.")
_TIMER_LAST = {"perf_counter", "monotonic", "perf_counter_ns"}
_HARMLESS_CALLS = {"append", "perf_counter", "monotonic", "perf_counter_ns",
                   "time", "range", "len", "print", "clear", "split", "join",
                   "round", "min", "max", "format"}
# first-arg names that mark a jitted function as a train step carrying
# donatable state
_STATE_ARG_NAMES = {"params", "state", "states", "opt_state", "train_state",
                    "syn0", "syn1", "syn1neg", "hist", "weights", "carry"}


def _is_timer_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and (last_part(node.func) in _TIMER_LAST
                 or dotted(node.func) == "time.time"))


def _is_sync_call(node: ast.Call) -> bool:
    lp = last_part(node.func)
    if lp in _SYNC_LAST:
        return True
    d = dotted(node.func)
    if d.startswith(_NP_PREFIXES) and lp in ("asarray", "array"):
        return True
    if isinstance(node.func, ast.Name) and node.func.id in ("float", "int"):
        return bool(node.args) and not isinstance(node.args[0], ast.Constant)
    return False


def _finding(ctx: ModuleContext, rule: str, node: ast.AST, message: str,
             hint: str) -> Finding:
    return Finding(rule, ctx.path, node.lineno, message, hint,
                   ctx.snippet(node.lineno))


# ------------------------------------------------------------ jit-host-sync ----

@register("jit-host-sync")
def jit_host_sync(ctx: ModuleContext) -> Iterable[Finding]:
    """float()/int()/.item()/np.asarray() on values inside traced bodies
    (breaks or silently syncs at trace time), and per-step device fetches
    in host loops around in-file jitted steps (serializes dispatch: every
    iteration waits for the device before enqueueing the next)."""
    out: List[Finding] = []
    for fn in ctx.traced:
        for call in ctx.walk_in_function(fn, ast.Call):
            flagged = None
            if (isinstance(call.func, ast.Name)
                    and call.func.id in ("float", "int") and call.args
                    and not isinstance(call.args[0], ast.Constant)):
                flagged = f"{call.func.id}() on a traced value"
            elif (isinstance(call.func, ast.Attribute)
                  and call.func.attr in ("item", "tolist")):
                flagged = f".{call.func.attr}() on a traced value"
            elif (dotted(call.func).startswith(_NP_PREFIXES)
                  and last_part(call.func) in ("asarray", "array")):
                flagged = f"{dotted(call.func)}() materializes inside a " \
                          "traced body"
            if flagged:
                out.append(_finding(
                    ctx, "jit-host-sync", call,
                    f"host sync inside traced code: {flagged}",
                    "keep the value as a jax array inside jit/shard_map/scan; "
                    "fetch on the host after the step returns"))
    # host-side loops: per-iteration fetch of an in-file jitted step's result
    for fn in ctx.functions:
        if fn in ctx.traced:
            continue
        for loop in ctx.walk_in_function(fn, (ast.For, ast.While)):
            bound: set = set()
            for node in ast.walk(loop):
                if (isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Call)
                        and isinstance(node.value.func, ast.Name)
                        and node.value.func.id in ctx.jitted_names):
                    for tgt in node.targets:
                        for el in ast.walk(tgt):
                            if isinstance(el, ast.Name):
                                bound.add(el.id)
            if not bound:
                continue
            for call in ast.walk(loop):
                if not isinstance(call, ast.Call):
                    continue
                is_fetch = (
                    (isinstance(call.func, ast.Name)
                     and call.func.id in ("float", "int") and call.args
                     and isinstance(call.args[0], ast.Name)
                     and call.args[0].id in bound)
                    or (isinstance(call.func, ast.Attribute)
                        and call.func.attr == "item"
                        and isinstance(call.func.value, ast.Name)
                        and call.func.value.id in bound))
                if is_fetch:
                    out.append(_finding(
                        ctx, "jit-host-sync", call,
                        "per-step device fetch inside the step loop "
                        "serializes dispatch (one round-trip per iteration)",
                        "accumulate on device and fetch once after the loop, "
                        "or fetch every N steps"))
    return out


# ---------------------------------------------------------- untimed-dispatch ----

@register("untimed-dispatch")
def untimed_dispatch(ctx: ModuleContext) -> Iterable[Finding]:
    """A perf_counter window that times calls without a device sync before
    the clock stops measures *enqueue*, not compute (JAX dispatch is
    async; on some transports even block_until_ready-free fetch paths
    return at enqueue — the class of bench bug BASELINE.md round 2 hit)."""
    out: List[Finding] = []
    for fn in ctx.functions:
        starts = {}  # var name -> max start lineno
        for node in ctx.walk_in_function(fn, ast.Assign):
            if (_is_timer_call(node.value) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                var = node.targets[0].id
                starts.setdefault(var, []).append(node.lineno)
        if not starts:
            continue
        for node in ctx.walk_in_function(fn, ast.BinOp):
            if not (isinstance(node.op, ast.Sub)
                    and isinstance(node.right, ast.Name)
                    and node.right.id in starts
                    and _is_timer_call(node.left)):
                continue
            stop_line = node.lineno
            cands = [ln for ln in starts[node.right.id] if ln < stop_line]
            if not cands:
                continue
            start_line = max(cands)
            work = False
            synced = False
            for call in ctx.walk_in_function(fn, ast.Call):
                if not (start_line < call.lineno <= stop_line):
                    continue
                if _is_timer_call(call):
                    continue
                if _is_sync_call(call):
                    synced = True
                elif last_part(call.func) not in _HARMLESS_CALLS:
                    work = True
            if work and not synced:
                out.append(_finding(
                    ctx, "untimed-dispatch", node,
                    "timed region stops the clock without a device sync — "
                    "this measures dispatch enqueue, not compute",
                    "block_until_ready the stage result (or fetch a scalar) "
                    "before reading the stop time"))
    return out


# --------------------------------------------------------------- prng-reuse ----

_NONCONSUMING = {"fold_in", "PRNGKey", "device_put", "block_until_ready",
                 "asarray", "print", "len", "str"}


def _is_key_source(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    d = dotted(node.func)
    return (d in ("PRNGKey", "split", "fold_in")
            or d.endswith(("random.PRNGKey", "random.split",
                           "random.fold_in", "random.key")))


@register("prng-reuse")
def prng_reuse(ctx: ModuleContext) -> Iterable[Finding]:
    """A PRNG key consumed twice without a split/fold_in in between draws
    the SAME randomness twice — silently correlated noise/negatives/
    dropout. Consumption = passing the key to any call that is not a
    derivation; ``key, sub = split(key)`` is the canonical advance and
    resets the count. Branch-aware: consumptions in different arms of the
    same ``if`` are mutually exclusive; a consumption inside a ``return``
    cannot flow to later code. A consumption inside a loop whose key was
    bound outside and never advanced in the loop body repeats randomness
    every iteration and is flagged."""
    out: List[Finding] = []
    for fn in ctx.functions:
        if isinstance(fn, ast.Lambda):
            continue
        uses_jax_random = any(
            dotted(n).startswith("jax.random")
            for n in ast.walk(fn) if isinstance(n, ast.Attribute))
        key_vars: set = set()
        args = fn.args
        if uses_jax_random:  # seed from key-ish param names only when the
            for a in (args.posonlyargs + args.args + args.kwonlyargs):
                if a.arg == "key" or a.arg.endswith("_key"):
                    key_vars.add(a.arg)
        rebind_stmts: List[ast.Assign] = []
        for node in ctx.walk_in_function(fn, ast.Assign):
            if _is_key_source(node.value):
                rebind_stmts.append(node)
                for tgt in node.targets:
                    for el in ast.walk(tgt):
                        if isinstance(el, ast.Name):
                            key_vars.add(el.id)
        if not key_vars:
            continue

        def stmt_targets(stmt: ast.Assign) -> set:
            return {el.id for t in stmt.targets for el in ast.walk(t)
                    if isinstance(el, ast.Name)}

        def rebinds(scope: ast.AST, var: str) -> bool:
            return any(var in stmt_targets(s) for s in rebind_stmts
                       if scope.lineno <= s.lineno
                       <= getattr(scope, "end_lineno", 1 << 30))

        def branch_sig(node: ast.AST):
            """[(id(if_node), arm), ...] for every enclosing If/Try arm."""
            sig = []
            cur = node
            while cur in ctx.parents:
                par = ctx.parents[cur]
                if isinstance(par, (ast.If, ast.Try)):
                    for arm_name in ("body", "orelse", "handlers",
                                     "finalbody"):
                        if cur in getattr(par, arm_name, []):
                            sig.append((id(par), arm_name))
                cur = par
            return sig

        def sigs_exclusive(a, b) -> bool:
            """True when the two consumptions sit in different arms of the
            same conditional — they cannot both execute."""
            arms_a = dict(a)
            return any(arms_a.get(if_id, arm) != arm for if_id, arm in b)

        def inside_return(node: ast.AST) -> bool:
            cur = node
            while cur in ctx.parents:
                cur = ctx.parents[cur]
                if isinstance(cur, (ast.Return, ast.Raise)):
                    return True
                if isinstance(cur, ast.stmt):
                    return False
            return False

        def terminal(node: ast.AST) -> bool:
            """The consumption's statement block ends in return/raise at or
            after it — the value cannot flow past this block (the
            sequential early-return dispatch pattern)."""
            stmt = node
            while stmt in ctx.parents and not isinstance(stmt, ast.stmt):
                stmt = ctx.parents[stmt]
            par = ctx.parents.get(stmt)
            for arm in ("body", "orelse", "handlers", "finalbody"):
                block = getattr(par, arm, None)
                if isinstance(block, list) and stmt in block:
                    rest = block[block.index(stmt):]
                    return any(isinstance(s, (ast.Return, ast.Raise))
                               for s in rest)
            return False

        def sig_within(outer, inner) -> bool:
            """Every arm of ``outer`` also encloses ``inner`` (the second
            consumption is in the same branch chain, or deeper)."""
            return all(item in inner for item in outer)

        loops = list(ctx.walk_in_function(fn, (ast.For, ast.While)))
        # (lineno, col, kind, var, node): rebinds clear, consumptions count
        events = []
        for stmt in rebind_stmts:
            for var in stmt_targets(stmt):
                events.append((stmt.lineno, getattr(stmt, "col_offset", 0),
                               0, var, stmt))
        for call in ctx.walk_in_function(fn, ast.Call):
            callee = last_part(call.func)
            if callee in _NONCONSUMING:
                continue
            for arg in list(call.args) + [kw.value for kw in call.keywords]:
                if not (isinstance(arg, ast.Name) and arg.id in key_vars):
                    continue
                if callee == "split":
                    stmt = ctx.parents.get(call)
                    while stmt is not None and not isinstance(stmt, ast.stmt):
                        stmt = ctx.parents.get(stmt)
                    if (isinstance(stmt, ast.Assign)
                            and arg.id in stmt_targets(stmt)):
                        continue  # `key, sub = split(key)`: the advance
                events.append((call.lineno, getattr(call, "col_offset", 0),
                               1, arg.id, call))

        consumed: dict = {}  # var -> (lineno, branch sig, terminal?)
        for lineno, _col, kind, var, node in sorted(events,
                                                    key=lambda e: e[:3]):
            if kind == 0:
                consumed.pop(var, None)
                continue
            sig = branch_sig(node)
            prior = consumed.get(var)
            loop_reuse = any(not rebinds(lp, var) for lp in loops
                             if lp.lineno <= lineno
                             <= getattr(lp, "end_lineno", 1 << 30))
            conflict = (prior is not None
                        and not sigs_exclusive(prior[1], sig)
                        # a terminal prior only flows to code in its own arm
                        and (not prior[2] or sig_within(prior[1], sig)))
            if conflict or loop_reuse:
                where = (f"already consumed at line {prior[0]}" if conflict
                         else "re-consumed every loop iteration without a "
                              "split/fold_in advance")
                out.append(_finding(
                    ctx, "prng-reuse", node,
                    f"PRNG key '{var}' {where} — identical randomness is "
                    "drawn twice",
                    "advance the key: `key, sub = jax.random.split(key)` "
                    "per use, or derive with fold_in"))
            elif prior is None and not inside_return(node):
                consumed[var] = (lineno, sig, terminal(node))
    return out


# -------------------------------------------------------------- stray-debug ----

@register("stray-debug")
def stray_debug(ctx: ModuleContext) -> Iterable[Finding]:
    """print()/jax.debug.* inside traced bodies: prints fire at TRACE time
    (misleading) or, for jax.debug.print, add host callbacks to the hot
    compiled step."""
    out: List[Finding] = []
    for fn in ctx.traced:
        for call in ctx.walk_in_function(fn, ast.Call):
            d = dotted(call.func)
            if (d == "print"
                    or d.endswith("debug.print")
                    or d.endswith("debug.breakpoint")
                    or d == "breakpoint"):
                out.append(_finding(
                    ctx, "stray-debug", call,
                    f"debug output `{d}` inside traced train-step code",
                    "remove it, or route through the telemetry metrics dict "
                    "fetched every N steps"))
    return out


# ------------------------------------------------------------ nondet-pytree ----

@register("nondet-pytree")
def nondet_pytree(ctx: ModuleContext) -> Iterable[Finding]:
    """Iterating a set where the order can reach a pytree/program structure
    makes tracing/compilation nondeterministic across processes (hash
    randomization) — the multi-host killer: two hosts compile different
    programs for 'the same' step."""
    out: List[Finding] = []

    def is_set_expr(node: ast.AST) -> bool:
        if isinstance(node, ast.Set):
            return True
        return (isinstance(node, ast.Call)
                and last_part(node.func) in ("set", "frozenset"))

    for node in ast.walk(ctx.tree):
        iters = []
        if isinstance(node, (ast.For, ast.AsyncFor)):
            iters = [node.iter]
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                               ast.GeneratorExp)):
            iters = [gen.iter for gen in node.generators]
        for it in iters:
            if is_set_expr(it):
                out.append(_finding(
                    ctx, "nondet-pytree", it,
                    "iteration over a set — order is nondeterministic across "
                    "processes and can leak into pytree/program structure",
                    "iterate `sorted(...)` of the set, or use a list/dict "
                    "(insertion-ordered)"))
    return out


# -------------------------------------------------------- env-read-in-trace ----

_BLESSED_ENV_PREFIX = "DL4J_TPU_"


@register("env-read-in-trace")
def env_read(ctx: ModuleContext) -> Iterable[Finding]:
    """os.environ/os.getenv reads outside the blessed seam (keys under
    the documented ``DL4J_TPU_*`` namespace — currently
    ``DL4J_TPU_ATTN_IMPL`` (ops/flash_attention.py attention-core chain),
    ``DL4J_TPU_MOE_IMPL`` (parallel/moe.py dispatch chain:
    alltoall | alltoall_2d | replicated),
    ``DL4J_TPU_UPDATE_SHARDING`` (optimize/updaters.py ZeRO
    update-sharding chain), ``DL4J_TPU_RUNPROF`` /
    ``DL4J_TPU_RUNPROF_DIR`` (telemetry/runprof.py ``runprof=`` seam
    default + session dump directory), and ``DL4J_TPU_FLEET_STALE_S`` /
    ``DL4J_TPU_FLEET_DEAD_S`` / ``DL4J_TPU_FLEET_POLL_S`` /
    ``DL4J_TPU_FLEET_HEARTBEAT_S`` (serve/router.py + serve/fleet.py
    membership timing defaults), all read host-side at
    trace/resolve time, never inside a traced body). Ad-hoc env reads are invisible config:
    they fork behavior between hosts and leak into traced code paths
    where a retrace won't see the change."""
    out: List[Finding] = []

    def blessed(key_node) -> bool:
        key = ctx.resolve_str(key_node) if key_node is not None else None
        return key is not None and key.startswith(_BLESSED_ENV_PREFIX)

    for node in ast.walk(ctx.tree):
        key_node = None
        hit = None
        if (isinstance(node, ast.Subscript)
                and dotted(node.value) == "os.environ"
                and isinstance(node.ctx, ast.Load)):
            key_node, hit = node.slice, "os.environ[...]"
        elif isinstance(node, ast.Call):
            d = dotted(node.func)
            if d == "os.environ.get" and node.args:
                key_node, hit = node.args[0], "os.environ.get"
            elif d == "os.getenv" and node.args:
                key_node, hit = node.args[0], "os.getenv"
        elif (isinstance(node, ast.Compare)
              and any(dotted(c) == "os.environ" for c in node.comparators)
              and len(node.ops) == 1
              and isinstance(node.ops[0], (ast.In, ast.NotIn))):
            key_node, hit = node.left, "`in os.environ`"
        if hit and not blessed(key_node):
            out.append(_finding(
                ctx, "env-read-in-trace", node,
                f"environment read ({hit}) outside the blessed seam",
                "route through a DL4J_TPU_*-namespaced knob; "
                "if this seam is deliberate, baseline it with a why"))
    return out


# ------------------------------------------------------------ missing-donate ----

@register("missing-donate")
def missing_donate(ctx: ModuleContext) -> Iterable[Finding]:
    """A jitted step whose leading args are params/opt-state must make an
    explicit donation decision: without ``donate_argnums`` every step
    holds two copies of the model (old + new params) in HBM. An explicit
    ``donate_argnums=()`` documents 'considered, declined' and passes."""
    out: List[Finding] = []

    def fn_carries_state(fn: ast.AST) -> bool:
        args = getattr(fn, "args", None)
        if args is None:
            return False
        names = [a.arg for a in (args.posonlyargs + args.args)][:3]
        return any(n in _STATE_ARG_NAMES for n in names)

    def call_has_donate(call: ast.Call) -> bool:
        return any(kw.arg in ("donate_argnums", "donate_argnames")
                   for kw in call.keywords)

    def flag(node, what):
        out.append(_finding(
            ctx, "missing-donate", node,
            f"jitted step {what} carries params/state with no "
            "donate_argnums decision",
            "donate the state args (`donate_argnums=(0,...)`) or declare "
            "`donate_argnums=()` to record that callers reuse the buffers"))

    # decorated defs
    for fn in ctx.functions:
        for deco in getattr(fn, "decorator_list", []):
            jit_names = [n for n in ast.walk(deco)
                         if isinstance(n, (ast.Name, ast.Attribute))
                         and last_part(n) == "jit"]
            if not jit_names or not fn_carries_state(fn):
                continue
            donate = (isinstance(deco, ast.Call) and call_has_donate(deco))
            if not donate:
                flag(fn, f"`{fn.name}`")
    # expression form: jax.jit(f, ...)
    for call in ast.walk(ctx.tree):
        if not (isinstance(call, ast.Call) and last_part(call.func) == "jit"
                and call.args):
            continue
        target = call.args[0]
        fns = []
        if isinstance(target, ast.Lambda):
            fns = [target]
        elif isinstance(target, ast.Name):
            fns = ctx.defs_by_name.get(target.id, [])
        if any(fn_carries_state(f) for f in fns) and not call_has_donate(call):
            name = getattr(target, "id", "<lambda>")
            flag(call, f"`jit({name})`")
    return out
