"""Golden fixtures for every graftlint rule: one known-bad and one
known-clean snippet each, pinned by rule id. These are the rule-level
contract; tests/test_graftlint_repo.py is the repo-level gate."""

import os
import sys
import textwrap

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.graftlint import lint_source  # noqa: E402


def _rules_hit(src: str, path: str = "fixture.py"):
    return {f.rule for f in lint_source(textwrap.dedent(src), path)}


# ------------------------------------------------------------ jit-host-sync ----

def test_jit_host_sync_bad_inside_jit():
    src = """
    import jax
    import numpy as np

    @jax.jit
    def step(params, x):
        y = params @ x
        norm = float(y.sum())          # host sync inside traced code
        host = np.asarray(y)           # materializes inside traced code
        return y / norm, host
    """
    assert "jit-host-sync" in _rules_hit(src)


def test_jit_host_sync_bad_scan_body():
    src = """
    import jax

    def epoch(params, xs):
        def body(carry, x):
            s = carry + x.sum().item()   # .item() in a lax.scan body
            return s, s
        return jax.lax.scan(body, params, xs)
    """
    assert "jit-host-sync" in _rules_hit(src)


def test_jit_host_sync_bad_host_loop_fetch():
    src = """
    import jax

    @jax.jit
    def train_step(params, x):
        return params - 0.1 * x, (params * x).sum()

    def fit(params, batches):
        total = 0.0
        for x in batches:
            params, loss = train_step(params, x)
            total += float(loss)       # per-step fetch serializes dispatch
        return params, total
    """
    assert "jit-host-sync" in _rules_hit(src)


def test_jit_host_sync_clean():
    src = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(params, x):
        y = params @ x
        return y / jnp.sum(y)

    def fit(params, batches):
        losses = []
        for x in batches:
            params, loss = step(params, x)
            losses.append(loss)        # stays on device
        return params, [float(l) for l in jax.device_get(losses)]
    """
    assert "jit-host-sync" not in _rules_hit(src)


# --------------------------------------------------------- untimed-dispatch ----

def test_untimed_dispatch_bad():
    src = """
    import time

    def bench(step, params, x):
        t0 = time.perf_counter()
        for _ in range(10):
            params, loss = step(params, x)
        return time.perf_counter() - t0   # clock stops at enqueue
    """
    assert "untimed-dispatch" in _rules_hit(src)


def test_untimed_dispatch_clean_block_until_ready():
    src = """
    import time
    import jax

    def bench(step, params, x):
        t0 = time.perf_counter()
        for _ in range(10):
            params, loss = step(params, x)
        jax.block_until_ready(params)
        return time.perf_counter() - t0
    """
    assert "untimed-dispatch" not in _rules_hit(src)


def test_untimed_dispatch_clean_scalar_fetch():
    src = """
    import time

    def bench(step, params, x):
        t0 = time.perf_counter()
        for _ in range(10):
            params, loss = step(params, x)
        last = float(loss)            # a device->host fetch is a true sync
        return time.perf_counter() - t0
    """
    assert "untimed-dispatch" not in _rules_hit(src)


# --------------------------------------------------------------- prng-reuse ----

def test_prng_reuse_bad_double_draw():
    src = """
    import jax

    def init(key):
        w1 = jax.random.normal(key, (4, 4))
        w2 = jax.random.normal(key, (4, 4))   # same key, same weights
        return w1, w2
    """
    assert "prng-reuse" in _rules_hit(src)


def test_prng_reuse_bad_loop_without_advance():
    src = """
    import jax

    def fit(step, params, key):
        key = jax.random.fold_in(key, 0)
        for i in range(10):
            params = step(params, key)   # identical randomness every step
        return params
    """
    assert "prng-reuse" in _rules_hit(src)


def test_prng_reuse_clean_split_and_branches():
    src = """
    import jax

    def fit(step, params, key):
        for i in range(10):
            key, sub = jax.random.split(key)
            params = step(params, sub)
        return params

    def init(key, kind):
        if kind == "normal":
            return jax.random.normal(key, (4,))
        return jax.random.uniform(key, (4,))   # other arm: exclusive
    """
    assert "prng-reuse" not in _rules_hit(src)


# -------------------------------------------------------------- stray-debug ----

def test_stray_debug_bad():
    src = """
    import jax

    @jax.jit
    def step(params, x):
        loss = (params * x).sum()
        print("loss", loss)            # fires at trace time only
        jax.debug.print("loss {}", loss)
        return loss
    """
    assert "stray-debug" in _rules_hit(src)


def test_stray_debug_clean_host_side():
    src = """
    import jax

    @jax.jit
    def step(params, x):
        return (params * x).sum()

    def fit(params, x):
        loss = step(params, x)
        print("loss", float(loss))     # host-side logging is fine
        return loss
    """
    assert "stray-debug" not in _rules_hit(src)


# ------------------------------------------------------------ nondet-pytree ----

def test_nondet_pytree_bad():
    src = """
    def build_params(names, init):
        return {n: init(n) for n in set(names)}   # nondeterministic order
    """
    assert "nondet-pytree" in _rules_hit(src)


def test_nondet_pytree_clean_sorted():
    src = """
    def build_params(names, init):
        return {n: init(n) for n in sorted(set(names))}
    """
    assert "nondet-pytree" not in _rules_hit(src)


# -------------------------------------------------------- env-read-in-trace ----

def test_env_read_bad():
    src = """
    import os

    def configure():
        return os.environ.get("MY_RANDOM_KNOB", "0") == "1"
    """
    assert "env-read-in-trace" in _rules_hit(src)


def test_env_read_clean_blessed():
    src = """
    import os

    ATTN_ENV = "DL4J_TPU_ATTN_IMPL"

    def configure():
        a = os.environ.get("DL4J_TPU_FOO")     # blessed namespace literal
        b = os.environ.get(ATTN_ENV)           # blessed via in-file constant
        return a, b
    """
    assert "env-read-in-trace" not in _rules_hit(src)


# ------------------------------------------------------------ missing-donate ----

def test_missing_donate_bad():
    src = """
    import jax

    @jax.jit
    def train_step(params, x):
        return params - 0.1 * x
    """
    assert "missing-donate" in _rules_hit(src)


def test_missing_donate_clean_donated_and_explicit_decline():
    src = """
    import jax
    from functools import partial

    @partial(jax.jit, donate_argnums=(0,))
    def train_step(params, x):
        return params - 0.1 * x

    @partial(jax.jit, donate_argnums=())   # considered, declined
    def oracle_step(params, x):
        return params - 0.1 * x
    """
    assert "missing-donate" not in _rules_hit(src)


# ------------------------------------------------------------- suppression ----

def test_inline_allow_requires_reason():
    bad = """
    import os

    def configure():
        return os.environ.get("KNOB")  # graftlint: allow[env-read-in-trace]
    """
    assert "env-read-in-trace" in _rules_hit(bad), \
        "a reason-less allow must NOT suppress"
    good = """
    import os

    def configure():
        return os.environ.get("KNOB")  # graftlint: allow[env-read-in-trace] deliberate seam because reasons
    """
    assert "env-read-in-trace" not in _rules_hit(good)


def test_parse_error_is_a_finding_not_a_crash():
    assert _rules_hit("def broken(:\n") == {"parse-error"}


# ============================================================================
# Concurrency rules (ISSUE 11) — bad+clean golden fixtures per rule, kept in
# module-level dicts so the meta-test below can pin that EVERY registered
# rule ships fixtures (a future rule cannot land unpinned).

BAD_FIXTURES = {
    "jit-host-sync": """
        import jax

        @jax.jit
        def step(params, x):
            return float((params * x).sum())
    """,
    "untimed-dispatch": """
        import time

        def bench(step, params, x):
            t0 = time.perf_counter()
            params, loss = step(params, x)
            return time.perf_counter() - t0
    """,
    "prng-reuse": """
        import jax

        def init(key):
            w1 = jax.random.normal(key, (4, 4))
            w2 = jax.random.normal(key, (4, 4))
            return w1, w2
    """,
    "stray-debug": """
        import jax

        @jax.jit
        def step(x):
            print("x", x)
            return x
    """,
    "nondet-pytree": """
        def build(names, init):
            return {n: init(n) for n in set(names)}
    """,
    "env-read-in-trace": """
        import os

        def configure():
            return os.environ.get("SOME_RANDOM_KNOB")
    """,
    "missing-donate": """
        import jax

        @jax.jit
        def train_step(params, x):
            return params - 0.1 * x
    """,
    "unguarded-shared-state": """
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
                self._thread = threading.Thread(target=self._loop)

            def start(self):
                self._thread.start()

            def _loop(self):
                while True:
                    self.count += 1       # thread-side write, no lock

            def snapshot(self):
                with self._lock:
                    return self.count     # a lock the writer never takes

            def stop(self):
                self._thread.join()
    """,
    "lock-order": """
        import threading

        a = threading.Lock()
        b = threading.Lock()

        def one():
            with a:
                with b:
                    pass

        def two():
            with b:
                with a:                   # reversed: deadlock risk
                    pass
    """,
    "blocking-under-lock": """
        import threading
        import time

        class Poller:
            def __init__(self, sock):
                self._lock = threading.Lock()
                self._sock = sock

            def poll(self):
                with self._lock:
                    return self._sock.recv(1024)   # blocks all contenders

            def backoff(self):
                with self._lock:
                    time.sleep(1.0)
    """,
    "unjoined-thread": """
        import threading

        class Sampler:
            def start(self):
                self._thread = threading.Thread(target=self._run,
                                                daemon=True)
                self._thread.start()

            def _run(self):
                pass

            def stop(self):
                pass                       # no join: teardown races _run
    """,
    "condition-wait-no-predicate": """
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._cond = threading.Condition(self._lock)
                self._ready = threading.Event()
                self.item = None

            def get(self):
                with self._cond:
                    self._cond.wait(1.0)   # spurious wakeup -> None
                    return self.item

            def get_event(self):
                self._ready.wait(0.5)      # result discarded
                return self.item
    """,
    "socket-no-timeout": """
        import socket
        import threading

        class Poller:
            def start(self):
                self._thread = threading.Thread(target=self._loop,
                                                daemon=True)
                self._thread.start()

            def _loop(self):
                sock = socket.socket()
                sock.connect(("127.0.0.1", 9000))  # no timeout anywhere
                return sock.recv(1024)

            def stop(self):
                self._thread.join(timeout=10)
    """,
    "unbounded-retry": """
        def fetch(sock):
            while True:
                try:
                    return sock.recv(1024)
                except ConnectionError:
                    continue              # dead peer -> infinite spin
    """,
    "retry-no-backoff": """
        def fetch(sock):
            for attempt in range(5):
                try:
                    return sock.recv(1024)
                except ConnectionError:
                    continue              # re-enters at CPU speed
            raise ConnectionError("gave up")
    """,
    "swallowed-thread-exception": """
        import threading

        class Pusher:
            def start(self):
                self._thread = threading.Thread(target=self._loop,
                                                daemon=True)
                self._thread.start()

            def _loop(self):
                try:
                    self._push()
                except Exception:
                    pass                  # the pusher dies invisibly

            def _push(self):
                pass

            def stop(self):
                self._thread.join(timeout=10)
    """,
    "nonidempotent-retry": """
        _IDEMPOTENT = frozenset({"get_kv", "put_kv"})
        _NONIDEMPOTENT = frozenset({"increment"})

        class Client:
            def _call(self, method, *args):
                return method, args

            def get_kv(self, key):
                return self._call("get_kv", key)

            def clear_all(self):
                return self._call("clear_all")  # classified by nobody
    """,
}

CLEAN_FIXTURES = {
    "jit-host-sync": """
        import jax

        @jax.jit
        def step(params, x):
            return (params * x).sum()
    """,
    "untimed-dispatch": """
        import time
        import jax

        def bench(step, params, x):
            t0 = time.perf_counter()
            params, loss = step(params, x)
            jax.block_until_ready(loss)
            return time.perf_counter() - t0
    """,
    "prng-reuse": """
        import jax

        def init(key):
            k1, k2 = jax.random.split(key)
            return jax.random.normal(k1, (4, 4)), jax.random.normal(k2, (4, 4))
    """,
    "stray-debug": """
        import jax

        @jax.jit
        def step(x):
            return x

        def fit(x):
            y = step(x)
            print("y", float(y))
            return y
    """,
    "nondet-pytree": """
        def build(names, init):
            return {n: init(n) for n in sorted(set(names))}
    """,
    "env-read-in-trace": """
        import os

        def configure():
            return os.environ.get("DL4J_TPU_SOME_KNOB")
    """,
    "missing-donate": """
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def train_step(params, x):
            return params - 0.1 * x
    """,
    "unguarded-shared-state": """
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
                self._thread = threading.Thread(target=self._loop)

            def start(self):
                self._thread.start()

            def _loop(self):
                while True:
                    with self._lock:
                        self.count += 1

            def snapshot(self):
                with self._lock:
                    return self.count

            def stop(self):
                self._thread.join()
    """,
    "lock-order": """
        import threading

        a = threading.Lock()
        b = threading.Lock()

        def one():
            with a:
                with b:
                    pass

        def two():
            with a:                        # same global order everywhere
                with b:
                    pass
    """,
    "blocking-under-lock": """
        import threading

        class Poller:
            def __init__(self, sock):
                self._lock = threading.Lock()
                self._sock = sock
                self._last = None

            def poll(self):
                data = self._sock.recv(1024)   # blocks OUTSIDE the lock
                with self._lock:
                    self._last = data
                return data
    """,
    "unjoined-thread": """
        import threading

        class Sampler:
            def start(self):
                self._stop = threading.Event()
                self._thread = threading.Thread(target=self._run,
                                                daemon=True)
                self._thread.start()

            def _run(self):
                while not self._stop.wait(0.1):
                    pass

            def stop(self):
                self._stop.set()
                self._thread.join(timeout=10)
    """,
    "condition-wait-no-predicate": """
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._cond = threading.Condition(self._lock)
                self._ready = threading.Event()
                self.item = None

            def get(self):
                with self._cond:
                    while self.item is None:   # predicate re-checked
                        self._cond.wait(1.0)
                    return self.item

            def get_event(self):
                if not self._ready.wait(0.5):  # result checked
                    raise TimeoutError
                return self.item
    """,
    "socket-no-timeout": """
        import socket
        import threading

        from deeplearning4j_tpu.utils import netwatch

        class Poller:
            def start(self):
                self._thread = threading.Thread(target=self._loop,
                                                daemon=True)
                self._thread.start()

            def _loop(self):
                sock = socket.create_connection(("127.0.0.1", 9000),
                                                timeout=5.0)
                sock.settimeout(5.0)
                data = sock.recv(1024)
                watched = netwatch.make_socket("poller.peer")
                watched.connect(("127.0.0.1", 9001))  # seam: default timed
                return data + watched.recv(1024)

            def stop(self):
                self._thread.join(timeout=10)
    """,
    "unbounded-retry": """
        import time

        def fetch(sock):
            for attempt in range(3):           # attempt budget
                try:
                    return sock.recv(1024)
                except ConnectionError:
                    time.sleep(0.1 * (attempt + 1))
            raise ConnectionError("gave up")

        def poll(sock, deadline):
            while True:
                if time.monotonic() > deadline:  # deadline guard
                    raise TimeoutError("poll deadline")
                try:
                    return sock.recv(1024)
                except ConnectionError:
                    time.sleep(0.05)
    """,
    "retry-no-backoff": """
        import random
        import time

        def fetch(sock):
            for attempt in range(5):
                try:
                    return sock.recv(1024)
                except ConnectionError:
                    time.sleep(0.05 * (2 ** attempt)
                               * (0.5 + random.random() / 2))
            raise ConnectionError("gave up")
    """,
    "swallowed-thread-exception": """
        import logging
        import threading

        log = logging.getLogger(__name__)

        class Pusher:
            def start(self):
                self._thread = threading.Thread(target=self._loop,
                                                daemon=True)
                self._thread.start()

            def _loop(self):
                try:
                    self._push()
                except Exception as exc:
                    log.warning("pusher died: %r", exc)

            def _push(self):
                pass

            def stop(self):
                self._thread.join(timeout=10)
    """,
    "nonidempotent-retry": """
        _IDEMPOTENT = frozenset({"get_kv", "put_kv"})
        _NONIDEMPOTENT = frozenset({"increment"})

        class Client:
            def _call(self, method, *args):
                return method, args

            def get_kv(self, key):
                return self._call("get_kv", key)

            def increment(self, key):
                return self._call("increment", key)
    """,
}


def _rule_params():
    import pytest as _pytest

    from tools.graftlint import RULES

    return _pytest.mark.parametrize("rule", sorted(RULES))


@_rule_params()
def test_bad_fixture_trips_its_rule(rule):
    assert rule in BAD_FIXTURES, f"no bad golden fixture for rule {rule!r}"
    assert rule in _rules_hit(BAD_FIXTURES[rule]), (
        f"the bad fixture for {rule!r} no longer trips it")


@_rule_params()
def test_clean_fixture_passes_its_rule(rule):
    assert rule in CLEAN_FIXTURES, f"no clean golden fixture for {rule!r}"
    assert rule not in _rules_hit(CLEAN_FIXTURES[rule]), (
        f"the clean fixture for {rule!r} falsely trips it")


def test_every_registered_rule_has_fixtures():
    """The meta-pin: a rule cannot register without shipping bad+clean
    goldens here — future rules land pinned or not at all."""
    from tools.graftlint import RULES

    assert set(BAD_FIXTURES) == set(RULES), (
        f"BAD_FIXTURES out of sync with the registry: "
        f"missing={set(RULES) - set(BAD_FIXTURES)}, "
        f"orphaned={set(BAD_FIXTURES) - set(RULES)}")
    assert set(CLEAN_FIXTURES) == set(RULES), (
        f"CLEAN_FIXTURES out of sync with the registry: "
        f"missing={set(RULES) - set(CLEAN_FIXTURES)}, "
        f"orphaned={set(CLEAN_FIXTURES) - set(RULES)}")


# ----------------------------------------- concurrency rule edge behavior ----

def test_condition_alias_guards_shared_state():
    """`Condition(self._lock)` IS the lock: guarding via the condition on
    one side and the lock on the other shares one underlying mutex."""
    src = """
    import threading

    class Engine:
        def __init__(self):
            self._lock = threading.RLock()
            self._work = threading.Condition(self._lock)
            self.queue = []
            self._thread = threading.Thread(target=self._loop)

        def start(self):
            self._thread.start()

        def submit(self, item):
            with self._work:
                self.queue.append(item)
                self._work.notify_all()

        def _loop(self):
            with self._lock:
                if self.queue:
                    self.queue.pop(0)

        def stop(self):
            self._thread.join()
    """
    assert "unguarded-shared-state" not in _rules_hit(src)


def test_lock_propagates_through_private_helpers():
    """A helper only ever called under the lock inherits the guard — the
    DecodeEngine._accept_token shape must not false-positive."""
    src = """
    import threading

    class Engine:
        def __init__(self):
            self._lock = threading.Lock()
            self.total = 0
            self._thread = threading.Thread(target=self._loop)

        def start(self):
            self._thread.start()

        def _bump(self):
            self.total += 1            # guarded at every call site

        def _loop(self):
            with self._lock:
                self._bump()

        def read(self):
            with self._lock:
                return self.total

        def stop(self):
            self._thread.join()
    """
    assert "unguarded-shared-state" not in _rules_hit(src)


def test_blocking_under_lock_allows_condition_wait():
    src = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._cond = threading.Condition(self._lock)
            self.item = None

        def get(self):
            with self._cond:
                while self.item is None:
                    self._cond.wait(0.1)   # releases while waiting: fine
                return self.item
    """
    assert "blocking-under-lock" not in _rules_hit(src)


def test_unjoined_thread_join_via_local_swap():
    """`t, self._thread = self._thread, None` then `t.join()` counts as a
    join path (the DecodeEngine.stop shape)."""
    src = """
    import threading

    class Engine:
        def start(self):
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

        def _loop(self):
            pass

        def stop(self):
            t, self._thread = self._thread, None
            if t is not None:
                t.join(timeout=10)
    """
    assert "unjoined-thread" not in _rules_hit(src)


def test_unjoined_thread_joined_via_list_loop():
    src = """
    import threading

    def fan_out(work):
        threads = [threading.Thread(target=w) for w in work]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    """
    assert "unjoined-thread" not in _rules_hit(src)


# ------------------------------------- net rule edge behavior (ISSUE 18) ----

def test_socket_timeout_propagates_through_alias():
    """`t = s; t.settimeout(5)` times the ONE underlying OS socket —
    reads through either name are clean."""
    src = """
    import socket
    import threading

    class Poller:
        def start(self):
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

        def _loop(self):
            raw = socket.socket()
            sock = raw
            sock.settimeout(5.0)
            return raw.recv(1024)      # timed through the alias

        def stop(self):
            self._thread.join(timeout=10)
    """
    assert "socket-no-timeout" not in _rules_hit(src)


def test_socket_timeout_propagates_through_call_params():
    """A module helper's socket parameter inherits timed-ness from its
    call sites: untimed at any site -> the helper's reads fire; timed at
    every site -> clean (the _recv_frame/_recv_exact chain shape)."""
    bad = """
    import socket
    import threading

    def _read(sock):
        return sock.recv(1024)

    class Poller:
        def start(self):
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

        def _loop(self):
            sock = socket.socket()
            return _read(sock)

        def stop(self):
            self._thread.join(timeout=10)
    """
    assert "socket-no-timeout" in _rules_hit(bad)
    good = """
    import socket
    import threading

    def _read(sock):
        return sock.recv(1024)

    class Poller:
        def start(self):
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

        def _loop(self):
            sock = socket.socket()
            sock.settimeout(5.0)
            return _read(sock)

        def stop(self):
            self._thread.join(timeout=10)
    """
    assert "socket-no-timeout" not in _rules_hit(good)


def test_netwatch_seam_is_timed_by_construction():
    """A socket adopted through utils.netwatch.wrap_socket carries the
    watch's enforced default — timed without a visible settimeout."""
    src = """
    import threading

    from deeplearning4j_tpu.utils import netwatch

    class Client:
        def start(self):
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

        def _loop(self):
            self._sock = netwatch.wrap_socket(self._dial(), "client")
            return self._sock.recv(1024)

        def _dial(self):
            return None

        def stop(self):
            self._thread.join(timeout=10)
    """
    assert "socket-no-timeout" not in _rules_hit(src)


def test_setdefaulttimeout_clears_the_module():
    src = """
    import socket
    import threading

    socket.setdefaulttimeout(10.0)

    def _loop():
        sock = socket.socket()
        return sock.recv(1024)

    def start():
        threading.Thread(target=_loop, daemon=True).start()
    """
    assert "socket-no-timeout" not in _rules_hit(src)


def test_handler_request_socket_needs_timeout():
    """socketserver handler: self.request IS the accepted socket; a
    `timeout` class attribute (or an explicit settimeout) times it."""
    bad = """
    import socketserver

    class Handler(socketserver.BaseRequestHandler):
        def handle(self):
            while True:
                data = self.request.recv(1024)
                if not data:
                    return
                self.request.sendall(data)
    """
    assert "socket-no-timeout" in _rules_hit(bad)
    good = """
    import socketserver

    class Handler(socketserver.BaseRequestHandler):
        timeout = 300

        def handle(self):
            while True:
                data = self.request.recv(1024)
                if not data:
                    return
                self.request.sendall(data)
    """
    assert "socket-no-timeout" not in _rules_hit(good)


def test_foreach_skip_scan_is_not_a_retry():
    """`except ... : continue` over a collection ADVANCES to the next
    item — only range()/count() loops (attempt budgets) and while loops
    are retry-shaped."""
    src = """
    def sweep(socks):
        out = []
        for sock in socks:
            try:
                out.append(sock.recv(1024))
            except ConnectionError:
                continue               # next peer, not a re-issue
        return out
    """
    hits = _rules_hit(src)
    assert "unbounded-retry" not in hits
    assert "retry-no-backoff" not in hits


def test_nonidempotent_contract_only_binds_declaring_modules():
    src = """
    class Client:
        def _call(self, method):
            return method

        def anything(self):
            return self._call("anything")   # no contract declared: free
    """
    assert "nonidempotent-retry" not in _rules_hit(src)
