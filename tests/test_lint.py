"""raftlint suite tests: every rule (R1-R10 JAX hazards + C1-C6 lock
discipline) fires on a seeded bad fixture and is silenced by ``# raftlint:
disable=RX``; good twins stay clean; the shape/dtype contract machinery
parses, enforces, and reports; the guard-annotation layer
(lint.concurrency.guarded_by) creates and honors guard maps; the CLI's
--diff/baseline/--list-suppressions satellite modes work end to end; the
SERVING.md threading model (hierarchy + lock table) is generated-checked
against the annotations; and the repo itself scans clean under --strict
(the CI gate, marked ``lint``).

No jax import is needed for the engine tests — the linter is pure AST.
"""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path
from typing import NamedTuple

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from raft_tpu.lint import contracts  # noqa: E402
from raft_tpu.lint.engine import (RULES, active_rules, scan_paths,  # noqa: E402
                                  scan_source)


def ids(findings):
    return {f.rule_id for f in findings}


# ---------------------------------------------------------------------------
# (rule_id, bad fixture, good twin) — the bad one MUST fire exactly that
# rule; the good twin must not.  Suppression is tested programmatically by
# appending the disable comment to every flagged line of the bad fixture.
# ---------------------------------------------------------------------------

FIXTURES = [
    ("R1", """
import jax

@jax.jit
def f(x):
    print("value is", x)
    return x * 2
""", """
import jax

@jax.jit
def f(x):
    jax.debug.print("value is {}", x)
    return x * 2
"""),
    ("R1", """
import jax

@jax.jit
def f(x):
    return float(x) * 2
""", """
import jax

@jax.jit
def f(x):
    return x.astype("float32") * 2
"""),
    ("R1", """
import jax

def body(carry, x):
    s = carry + x.item()
    return s, s

def run(xs):
    import jax.numpy as jnp
    return jax.lax.scan(body, jnp.float32(0), xs)
""", """
import jax

def body(carry, x):
    s = carry + x
    return s, s

def run(xs):
    import jax.numpy as jnp
    return jax.lax.scan(body, jnp.float32(0), xs)
"""),
    ("R2", """
import jax

def run(fn, batches):
    out = []
    for b in batches:
        out.append(jax.jit(fn)(b))
    return out
""", """
import jax

def run(fn, batches):
    jfn = jax.jit(fn)
    out = []
    for b in batches:
        out.append(jfn(b))
    return out
"""),
    ("R2", """
import jax
import jax.numpy as jnp

@jax.jit
def make_mask(n):
    return jnp.zeros(n)
""", """
import functools

import jax
import jax.numpy as jnp

@functools.partial(jax.jit, static_argnames=("n",))
def make_mask(n):
    return jnp.zeros(n)
"""),
    ("R3", """
import jax

def load_params(path):
    return jax.random.PRNGKey(0)
""", """
import jax

def load_params(path, seed):
    return jax.random.PRNGKey(seed)
"""),
    ("R3", """
import jax

def augment(key, img):
    a = jax.random.normal(key, img.shape)
    b = jax.random.uniform(key, img.shape)
    return img + a * b
""", """
import jax

def augment(key, img):
    key, sub = jax.random.split(key)
    a = jax.random.normal(sub, img.shape)
    b = jax.random.uniform(key, img.shape)
    return img + a * b
"""),
    ("R4", """
import jax.numpy as jnp

def zeros_like_flow(h, w):
    return jnp.zeros((h, w, 2), dtype=jnp.float64)
""", """
import jax.numpy as jnp

def zeros_like_flow(h, w):
    return jnp.zeros((h, w, 2), dtype=jnp.float32)
"""),
    ("R4", """
import jax.numpy as jnp

def roundtrip(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)
""", """
import jax.numpy as jnp

def single_cast(x):
    return x.astype(jnp.float32)
"""),
    ("R5", """
import jax.numpy as jnp

def normalize(flow, mag):
    return jnp.where(mag > 0, flow / mag, 0.0)
""", """
import jax.numpy as jnp

def normalize(flow, mag):
    safe = jnp.where(mag > 0, mag, 1.0)
    return jnp.where(mag > 0, flow / safe, 0.0)
"""),
    ("R6", """
import jax
import numpy as np

@jax.jit
def step(state, batch):
    loss = np.asarray(state).mean()
    return state, loss
""", """
import jax
import jax.numpy as jnp

@jax.jit
def step(state, batch):
    loss = jnp.asarray(state).mean()
    return state, loss
"""),
    ("R6", """
import jax

@jax.jit
def step(state):
    return jax.device_get(state)
""", """
import jax

@jax.jit
def step(state):
    return state

def log(state):
    return jax.device_get(state)
"""),
    ("R7", """
import jax

def train(make_step, state, batches):
    step = jax.jit(make_step, donate_argnums=0)
    for b in batches:
        new_state, metrics = step(state, b)
    return state
""", """
import jax

def train(make_step, state, batches):
    step = jax.jit(make_step, donate_argnums=0)
    for b in batches:
        state, metrics = step(state, b)
    return state
"""),
    ("R8", """
import jax

def unroll(coords, deltas):
    def body(carry, d):
        coords = carry
        coords = coords + d
        return coords, coords
    return jax.lax.scan(body, coords, deltas)
""", """
import jax

def unroll(coords, deltas):
    def body(carry, d):
        coords = jax.lax.stop_gradient(carry)
        coords = coords + d
        return coords, coords
    return jax.lax.scan(body, coords, deltas)
"""),
    ("R9", """
from raft_tpu.lint.contracts import contract

@contract(x="f32[B,H,")
def f(x):
    return x
""", """
from raft_tpu.lint.contracts import contract

@contract(x="f32[B,H,W,2]")
def f(x):
    return x
"""),
    ("R9", """
from raft_tpu.lint.contracts import contract

@contract(coords="f32[B,2]")
def f(x):
    return x
""", """
from raft_tpu.lint.contracts import contract

@contract(x="f32[B,2]")
def f(x, radius=1):
    return x
"""),
    ("R10", """
def load_dataset(path, verbose=True):
    if verbose:
        print("scanning", path)
    return path
""", """
from raft_tpu.telemetry.log import get_logger

_log = get_logger("data")


def load_dataset(path, verbose=True):
    if verbose:
        _log.info(f"scanning {path}")
    return path
"""),
    # ---- the concurrency family (C1-C6): lock-holding classes only ----
    ("C1", """
import threading

class Stats:
    def __init__(self):
        self._lock = threading.Lock()
        self.items = {}

    def add(self, k, v):
        with self._lock:
            self.items[k] = v

    def reset(self):
        self.items = {}
""", """
import threading

class Stats:
    def __init__(self):
        self._lock = threading.Lock()
        self.items = {}

    def add(self, k, v):
        with self._lock:
            self.items[k] = v

    def reset(self):
        with self._lock:
            self.items = {}
"""),
    ("C2", """
import threading
import time

class Poller:
    def __init__(self):
        self._lock = threading.Lock()
        self.value = 0

    def refresh(self):
        with self._lock:
            time.sleep(0.1)
            self.value += 1
""", """
import threading
import time

class Poller:
    def __init__(self):
        self._lock = threading.Lock()
        self.value = 0

    def refresh(self):
        time.sleep(0.1)
        with self._lock:
            self.value += 1
"""),
    ("C3", """
import threading

class FeatureStore:
    def __init__(self, tripper):
        self._lock = threading.Lock()
        self.tripper = tripper
        self.n = 0

    def evict_one(self):
        with self._lock:
            self.tripper.trip()

class Tripper:
    def __init__(self, store):
        self._lock = threading.Lock()
        self.store = store
        self.n = 0

    def trip(self):
        with self._lock:
            self.n += 1

    def open_all(self):
        with self._lock:
            self.store.evict_one()
""", """
import threading

class FeatureStore:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def evict_one(self):
        with self._lock:
            self.n += 1

class Tripper:
    def __init__(self, store):
        self._lock = threading.Lock()
        self.store = store
        self.n = 0

    def trip(self):
        with self._lock:
            self.n += 1

    def open_all(self):
        with self._lock:
            self.store.evict_one()
"""),
    ("C4", """
import threading

class Inbox:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self.items = []

    def take(self):
        with self._cond:
            if not self.items:
                self._cond.wait()
            return self.items.pop()
""", """
import threading

class Inbox:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self.items = []

    def take(self):
        with self._cond:
            while not self.items:
                self._cond.wait()
            return self.items.pop()
"""),
    ("C5", """
import threading

class LazyCache:
    def __init__(self):
        self._lock = threading.Lock()
        self._cache = {}

    def lookup(self, key):
        if key not in self._cache:
            self._cache[key] = key * 2
        return self._cache[key]
""", """
import threading

class LazyCache:
    def __init__(self):
        self._lock = threading.Lock()
        self._cache = {}

    def lookup(self, key):
        with self._lock:
            if key not in self._cache:
                self._cache[key] = key * 2
            return self._cache[key]
"""),
    ("C6", """
import threading

class Meter:
    def __init__(self):
        self._lock = threading.Lock()
        self.calls = 0

    def record(self):
        self.calls += 1
""", """
import threading

class Meter:
    def __init__(self):
        self._lock = threading.Lock()
        self.calls = 0

    def record(self):
        with self._lock:
            self.calls += 1
"""),
    ("B1", """
import jax

def make_handler(fn):
    step = jax.jit(fn)

    def handle(request):
        img = request["image"]
        return step(img)
    return handle
""", """
import jax

def make_handler(fn, sconfig, pad_to_bucket):
    step = jax.jit(fn)

    def handle(request):
        img = request["image"]
        bucket = sconfig.route(img.shape[0], img.shape[1])
        padded = pad_to_bucket(img, bucket)
        return step(padded)
    return handle
"""),
    ("B2", """
class Engine:
    def warmup(self):
        for kind in ("pair", "encode"):
            self._compile(kind)

    def _compile(self, kind):
        if kind == "pair":
            return self._pair()
        if kind == "encode":
            return self._encode()
        if kind == "stream":
            return self._stream()
""", """
class Engine:
    def warmup(self):
        for kind in ("pair", "encode", "stream"):
            self._compile(kind)

    def _compile(self, kind):
        if kind == "pair":
            return self._pair()
        if kind == "encode":
            return self._encode()
        if kind == "stream":
            return self._stream()
"""),
    ("B2", """
class Engine:
    def warmup(self):
        for key in enumerate_warmup_grid(self.config, self.sconfig):
            self._compile(key)

    def _compile(self, kind):
        if kind == "pair":
            return self._pair()
        if kind == "spoison2":
            return self._poison()

def enumerate_warmup_grid(config, sconfig):
    return [("pair", 432, 1024, 1, "fixed")]
""", """
class Engine:
    def warmup(self):
        for key in enumerate_warmup_grid(self.config, self.sconfig):
            self._compile(key)

    def _compile(self, kind):
        if kind == "pair":
            return self._pair()
        if kind == "spoison2":
            return self._poison()

def enumerate_warmup_grid(config, sconfig):
    return [("pair", 432, 1024, 1, "fixed"),
            ("spoison2", 432, 1024, 1, "fixed")]
"""),
    ("B3", """
import jax.numpy as jnp

def handle_flow(request):
    canvas = jnp.zeros((8, 8, 3), jnp.float32)
    return canvas
""", """
import numpy as np

def handle_flow(request):
    canvas = np.zeros((8, 8, 3), np.float32)
    return canvas
"""),
    ("B4", """
VMEM_LIMIT = 16 * 1024 * 1024

def fits(nbytes):
    return nbytes <= VMEM_LIMIT
""", """
from raft_tpu.kernel_plans import VMEM_BYTES

def fits(nbytes):
    return nbytes <= VMEM_BYTES
"""),
    # B2 cache extension: a kind covered only by export_cache (the AOT
    # serialization surface) counts as warmed — it deserializes at boot
    ("B2", """
class Engine:
    def warmup(self):
        for kind in ("pair",):
            self._compile(kind)

    def _compile(self, kind):
        if kind == "pair":
            return self._pair()
        if kind == "cached":
            return self._cached()
""", """
class Engine:
    def warmup(self):
        for kind in ("pair",):
            self._compile(kind)

    def export_cache(self):
        for kind in ("pair", "cached"):
            self._save(kind)

    def _compile(self, kind):
        if kind == "pair":
            return self._pair()
        if kind == "cached":
            return self._cached()
"""),
    ("B5", """
KEY_FIELDS = ("kind", "h", "w", "b")

def enumerate_warmup_grid(config, sconfig):
    keys = []
    for (h, w, b, kind) in grid(config, sconfig):
        key = (kind, h, w, b, policy)
        keys.append(key)
    return keys
""", """
KEY_FIELDS = ("kind", "h", "w", "b", "policy")

def enumerate_warmup_grid(config, sconfig):
    keys = []
    for (h, w, b, kind) in grid(config, sconfig):
        key = (kind, h, w, b, policy)
        keys.append(key)
    return keys
"""),
]


@pytest.mark.parametrize("rule_id,bad,good",
                         FIXTURES, ids=[f"{r}-{i}" for i, (r, _, _)
                                        in enumerate(FIXTURES)])
def test_rule_fires_and_good_twin_clean(rule_id, bad, good):
    bad_findings = scan_source(bad)
    assert rule_id in ids(bad_findings), \
        f"{rule_id} did not fire on its bad fixture"
    assert rule_id not in ids(scan_source(good)), \
        f"{rule_id} fired on its good twin"


@pytest.mark.parametrize("rule_id,bad,good",
                         FIXTURES, ids=[f"{r}-{i}" for i, (r, _, _)
                                        in enumerate(FIXTURES)])
def test_suppression_comment_silences(rule_id, bad, good):
    findings = [f for f in scan_source(bad) if f.rule_id == rule_id]
    assert findings
    lines = bad.splitlines()
    for f in findings:
        lines[f.line - 1] += f"  # raftlint: disable={rule_id}"
    assert rule_id not in ids(scan_source("\n".join(lines)))


def test_suppress_all_and_file_level():
    bad = FIXTURES[0][1]
    findings = scan_source(bad)
    line = findings[0].line
    lines = bad.splitlines()
    lines[line - 1] += "  # raftlint: disable=all"
    assert not scan_source("\n".join(lines))
    assert not scan_source("# raftlint: disable-file=R1\n" + bad)


def test_directive_inside_string_literal_does_not_suppress():
    # a disable directive spelled in a docstring/string must NOT defeat the
    # gate — only real comment tokens count
    bad = FIXTURES[0][1]
    assert "R1" in ids(scan_source(
        '"""docs say: # raftlint: disable-file=R1"""\n' + bad))
    assert "R1" in ids(scan_source(
        "x = '# raftlint: disable=all'\n" + bad))


def test_aliased_contract_import_still_checked_by_r9():
    src = """
from raft_tpu.lint.contracts import contract as shape_spec

@shape_spec(coords="f32[B,")
def f(coords):
    return coords
"""
    assert "R9" in ids(scan_source(src))


@pytest.mark.parametrize("path,fires", [
    ("raft_tpu/kernel_plans.py", False),    # what the kernels ask for
    ("raft_tpu/lint/budget.py", False),     # what the devices hold
    ("raft_tpu/ops/corr_pallas.py", True),  # a kernel: it reads its plan
    ("raft_tpu/serving/engine.py", True),
])
def test_b4_byte_constants_have_two_homes(path, fires):
    src = "VMEM_BYTES = 32 * 1024 * 1024\n"
    assert ("B4" in ids(scan_source(src, path=path))) == fires


def test_r10_cli_surfaces_exempt():
    """print() is the PRODUCT on CLI surfaces: files named cli.py, files
    with a __main__ guard (every tools/ script), and main/*_cli handler
    functions all keep printing; library code does not."""
    bare = "def helper(x):\n    print(x)\n    return x\n"
    assert "R10" in ids(scan_source(bare))
    # same code in a file named cli.py -> exempt
    assert "R10" not in ids(scan_source(bare, path="raft_tpu/cli.py"))
    # a script (top-level __main__ guard anywhere in the file) -> exempt
    script = bare + "\nif __name__ == \"__main__\":\n    helper(1)\n"
    assert "R10" not in ids(scan_source(script, path="tools/thing.py"))
    # CLI handler functions by naming convention -> exempt
    assert "R10" not in ids(scan_source(
        "def main():\n    print('usage')\n"))
    assert "R10" not in ids(scan_source(
        "def train_cli(args):\n    print('step')\n"))
    # ...but only for the handler itself, not its file's other functions
    assert "R10" in ids(scan_source(
        "def train_cli(args):\n    print('ok')\n\n"
        "def library_fn(x):\n    print(x)\n"))


def test_r10_traced_print_is_r1s_domain():
    """A print inside jit-traced code is a trace-time side effect (R1), not
    a logging-style violation — R10 must not double-report it."""
    src = """
import jax

@jax.jit
def f(x):
    print("traced", x)
    return x
"""
    found = ids(scan_source(src))
    assert "R1" in found
    assert "R10" not in found


def test_c1_guarded_by_annotation_creates_and_silences_guards():
    """The explicit annotation layer: a class-level guarded_by() puts an
    attribute in the guard map even when inference can't see it, and a
    @guarded_by method decorator marks its whole body as lock-held."""
    src = """
import threading
from raft_tpu.lint.concurrency import guarded_by

class Engine:
    hits = guarded_by("_lock")

    def __init__(self):
        self._lock = threading.Lock()
        self.hits = 0

    def bump(self):
        self.hits = self.hits + 1
"""
    assert "C1" in ids(scan_source(src))
    fixed = src.replace("    def bump(self):",
                        "    @guarded_by(\"_lock\")\n    def bump(self):")
    assert "C1" not in ids(scan_source(fixed))


def test_c2_wait_while_holding_second_lock():
    """Waiting on our own condition with exactly its lock held is the
    protocol; holding ANOTHER lock across the wait blocks every thread."""
    ok = """
import threading

class Q:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self.items = []

    def take(self):
        with self._cond:
            while not self.items:
                self._cond.wait()
"""
    assert "C2" not in ids(scan_source(ok))
    bad = """
import threading

class Q:
    def __init__(self):
        self._lock = threading.Lock()
        self._other = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self.items = []

    def take(self):
        with self._other:
            with self._cond:
                while not self.items:
                    self._cond.wait()
"""
    assert "C2" in ids(scan_source(bad))


def test_c3_self_deadlock_and_declared_hierarchy_inversion():
    deadlock = """
import threading

class E:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def run(self):
        with self._lock:
            with self._lock:
                self.n += 1
"""
    found = [f for f in scan_source(deadlock) if f.rule_id == "C3"]
    assert found and "re-acquires" in found[0].message
    # class/lock names from the DECLARED serving hierarchy
    # (lint.concurrency.SERVING_LOCK_HIERARCHY): store holds its lock and
    # calls into the breaker -> inner-acquires an OUTER lock = inversion,
    # flagged before any cycle exists
    inversion = """
import threading

class SessionStore:
    def __init__(self, breaker):
        self._lock = threading.Lock()
        self.breaker = breaker
        self.n = 0

    def sweep_all(self):
        with self._lock:
            self.breaker.trip_now()

class CircuitBreaker:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def trip_now(self):
        with self._lock:
            self.n += 1
"""
    found = [f for f in scan_source(inversion) if f.rule_id == "C3"]
    assert found and "inversion" in found[0].message


def test_c_rules_scoped_to_lock_holding_classes():
    """No lock declared = no shared-state statement = no C findings, even
    for patterns that would fire on a threaded class."""
    src = """
class Plain:
    def __init__(self):
        self.cache = {}
        self.calls = 0

    def lookup(self, k):
        if k not in self.cache:
            self.cache[k] = k * 2
        self.calls += 1
        return self.cache[k]
"""
    assert not {r for r in ids(scan_source(src)) if r.startswith("C")}


def test_watched_lock_constructor_counts_as_a_lock():
    """Serving locks are created via telemetry.watchdogs.watched_lock —
    the analysis must keep seeing them as locks or the whole C family
    goes blind exactly where it matters."""
    src = """
from raft_tpu.telemetry.watchdogs import watched_lock

class Store:
    def __init__(self):
        self._lock = watched_lock("Store._lock")
        self.items = {}

    def put(self, k, v):
        with self._lock:
            self.items[k] = v

    def wipe(self):
        self.items = {}
"""
    assert "C1" in ids(scan_source(src))


def test_serving_lock_hierarchy_is_consistent_with_static_edges():
    """The declared hierarchy (annotated in the serving modules, armed
    into the runtime validator) must agree with every statically
    extracted acquisition edge of the actual serving code."""
    from raft_tpu.lint import concurrency as conc
    from raft_tpu.lint.engine import FileContext, iter_python_files
    all_classes = []
    for f in iter_python_files([str(REPO / "raft_tpu")]):
        ctx = FileContext(str(f), f.read_text(encoding="utf-8"))
        all_classes.extend((ctx, c) for c in conc.analyze_classes(ctx))
    edges, _ = conc.build_lock_graph(all_classes)
    assert not conc.find_cycles(edges)
    for src, dst, node, path in edges:
        rs, rd = conc.hierarchy_rank(src), conc.hierarchy_rank(dst)
        if rs is not None and rd is not None:
            assert rs < rd, (f"edge {src} -> {dst} at {path}:"
                             f"{node.lineno} inverts the declared "
                             f"hierarchy")


def test_eight_plus_distinct_rules_covered():
    active_rules()
    covered = {r for r, _, _ in FIXTURES}
    assert len(covered) >= 8
    assert covered == set(RULES), \
        "every registered rule needs a bad/good fixture pair"


def test_select_and_ignore():
    bad = FIXTURES[0][1]
    assert ids(scan_source(bad, select=["R3"])) == set()
    assert "R1" not in ids(scan_source(bad, ignore=["R1"]))
    with pytest.raises(KeyError):
        active_rules(select=["R99"])


def test_syntax_error_is_reported_not_raised():
    findings = scan_source("def broken(:\n  pass")
    assert [f.rule_id for f in findings] == ["E999"]


def test_alias_resolution_variants():
    src = """
from jax import numpy as weird
from jax.random import PRNGKey as mk

def f():
    k = mk(0)
    return weird.zeros((3,), dtype=weird.float64)
"""
    got = ids(scan_source(src))
    assert "R3" in got and "R4" in got


# ---------------------------------------------------------------------------
# contracts
# ---------------------------------------------------------------------------

def test_parse_spec_accepts_and_rejects():
    s = contracts.parse_spec("bf16|f32[B,...,2]")
    assert s.dtypes == ("bfloat16", "float32")
    assert s.dims == ("B", "...", 2)
    for bad in ("f32[B", "q99[B]", "f32[b]", "f32[...,...]", "[B,?]"):
        with pytest.raises(contracts.ContractError):
            contracts.parse_spec(bad)


def test_contract_rejects_unknown_parameter_at_decoration():
    with pytest.raises(contracts.ContractError):
        @contracts.contract(nope="f32[B]")
        def f(x):
            return x


@pytest.fixture
def checked():
    contracts.enable_checking(True)
    yield
    contracts.enable_checking(False)


def test_contract_runtime_checks(checked):
    import numpy as np

    @contracts.contract(a="f32[B,N]", b="f32[B,N]", _returns="f32[B,N]")
    def add(a, b):
        return a + b

    x = np.zeros((2, 3), np.float32)
    assert add(x, x).shape == (2, 3)
    with pytest.raises(contracts.ContractError, match="B=2"):
        add(x, np.zeros((4, 3), np.float32))      # inconsistent symbol
    with pytest.raises(contracts.ContractError, match="dtype"):
        add(x, np.zeros((2, 3), np.float64))
    with pytest.raises(contracts.ContractError, match="rank"):
        add(x, np.zeros((2, 3, 1), np.float32))


def test_contract_dotted_and_none_and_disabled():
    import numpy as np

    class Batch(NamedTuple):
        image: object
        flow: object

    @contracts.contract({"batch.image": "f32[B,H,W,3]",
                         "batch.flow": "f32[B,H,W,2]"}, extra="f32[B]")
    def step(batch, extra=None):
        return batch.image

    good = Batch(np.zeros((1, 8, 8, 3), np.float32),
                 np.zeros((1, 8, 8, 2), np.float32))
    bad = Batch(np.zeros((1, 8, 8, 3), np.float32),
                np.zeros((2, 8, 8, 2), np.float32))
    contracts.enable_checking(False)
    step(bad)                                      # disabled -> passes through
    contracts.enable_checking(True)
    try:
        step(good)                                 # None extra is skipped
        with pytest.raises(contracts.ContractError):
            step(bad)
    finally:
        contracts.enable_checking(False)


def test_dotted_contract_on_missing_field_raises(checked):
    import numpy as np

    class Batch(NamedTuple):
        image: object

    @contracts.contract({"batch.imgae": "f32[B,H,W,3]"})   # typo'd on purpose
    def step(batch):
        return batch.image

    with pytest.raises(contracts.ContractError, match="no such field"):
        step(Batch(np.zeros((1, 4, 4, 3), np.float32)))


def test_env_var_parsed_tolerantly():
    for val, expect in (("true", "True"), ("1", "True"), ("YES", "True"),
                        ("0", "False"), ("nonsense", "False"), ("", "False")):
        r = subprocess.run(
            [sys.executable, "-c",
             "from raft_tpu.lint import contracts; "
             "print(contracts.checking_enabled())"],
            capture_output=True, text=True, cwd=str(REPO),
            env={**__import__('os').environ,
                 "RAFT_TPU_CHECK_CONTRACTS": val})
        assert r.returncode == 0, r.stderr
        assert r.stdout.strip() == expect, (val, r.stdout, r.stderr)


def test_contracts_survive_jit_tracing():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    @contracts.contract(x="f32[B,N]", _returns="f32[B,N]")
    def double(x):
        return x * 2

    contracts.enable_checking(True)
    try:
        out = jax.jit(double)(jnp.ones((2, 5), jnp.float32))
        assert out.shape == (2, 5)
        with pytest.raises(contracts.ContractError):
            jax.jit(double)(jnp.ones((2, 5), jnp.bfloat16))
    finally:
        contracts.enable_checking(False)


def test_fused_kernel_contract_pins_float32():
    """Satellite audit (ops/corr_pallas.py): the fused lookup takes the
    feature maps in float32 or bfloat16 (its MXU passes follow from the
    dtype) and keeps coords float32 on the CPU (interpret) backend; the
    output is float32 unless its caller states bfloat16 (``out_dtype``: the
    float32 sums rounded as they are written) and nothing else — enforced
    by its contract."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from raft_tpu.ops.corr import fmap2_pyramid
    from raft_tpu.ops.corr_pallas import _fused_lookup_impl

    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    f1 = jax.random.normal(k1, (1, 8, 8, 16), jnp.float32)
    f2 = jax.random.normal(k2, (1, 8, 8, 16), jnp.float32)
    coords = jnp.zeros((1, 8, 8, 2), jnp.float32) + 3.5
    contracts.enable_checking(True)
    try:
        out = _fused_lookup_impl(f1, fmap2_pyramid(f2, 2), coords, 2)
        assert out.dtype == jnp.float32
        out = _fused_lookup_impl(f1.astype(jnp.bfloat16),
                                 fmap2_pyramid(f2, 2), coords, 2)
        assert out.dtype == jnp.float32
        out = _fused_lookup_impl(f1, fmap2_pyramid(f2, 2), coords, 2,
                                 out_dtype=jnp.bfloat16)
        assert out.dtype == jnp.bfloat16
        with pytest.raises(contracts.ContractError):
            _fused_lookup_impl(f1, fmap2_pyramid(f2, 2), coords, 2,
                               out_dtype=jnp.float16)
        with pytest.raises(contracts.ContractError):
            _fused_lookup_impl(f1.astype(jnp.float16),
                               fmap2_pyramid(f2, 2), coords, 2)
        with pytest.raises(contracts.ContractError):
            _fused_lookup_impl(f1, fmap2_pyramid(f2, 2),
                               coords.astype(jnp.bfloat16), 2)
    finally:
        contracts.enable_checking(False)


# ---------------------------------------------------------------------------
# CLI: --diff changed-files mode, findings baseline, suppression audit
# ---------------------------------------------------------------------------

RAFTLINT = str(REPO / "tools" / "raftlint.py")
BAD_PRNG = "import jax\nk = jax.random.PRNGKey(0)\n"


def _run(args, cwd=None):
    return subprocess.run([sys.executable, RAFTLINT, *args],
                          capture_output=True, text=True, cwd=cwd)


@pytest.fixture
def tmp_git_repo(tmp_path):
    """A throwaway git repo with one committed clean file."""
    def git(*a):
        r = subprocess.run(["git", "-c", "user.email=t@t", "-c",
                            "user.name=t", *a], cwd=tmp_path,
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        return r.stdout
    git("init", "-q")
    (tmp_path / "clean.py").write_text("x = 1\n")
    git("add", "clean.py")
    git("commit", "-qm", "seed")
    return tmp_path, git


def test_diff_mode_scans_only_changed_files(tmp_git_repo, monkeypatch):
    tmp_path, git = tmp_git_repo
    import tools.raftlint as rl
    monkeypatch.setattr(rl, "REPO_ROOT", tmp_path)
    # nothing changed: clean exit, clean.py not rescanned
    assert rl.main(["--diff", "HEAD", "--strict", str(tmp_path)]) == 0
    # a changed tracked file with a finding fails the strict diff gate
    (tmp_path / "clean.py").write_text(BAD_PRNG)
    assert rl.main(["--diff", "HEAD", "--strict", str(tmp_path)]) == 1
    # an untracked file is scanned too (pre-commit covers new files)
    git("checkout", "-q", "--", "clean.py")
    (tmp_path / "fresh.py").write_text(BAD_PRNG)
    assert rl.main(["--diff", "HEAD", "--strict", str(tmp_path)]) == 1


def test_baseline_accepts_known_findings_not_new_ones(tmp_path, monkeypatch):
    import tools.raftlint as rl
    monkeypatch.setattr(rl, "REPO_ROOT", tmp_path)
    bad = tmp_path / "legacy.py"
    bad.write_text(BAD_PRNG)
    baseline = tmp_path / "LINT_BASELINE.json"
    # accept the current findings, then the gate passes on them
    assert rl.main(["--write-baseline", "--baseline", str(baseline),
                    str(bad)]) == 0
    assert baseline.exists()
    assert rl.main(["--strict", "--baseline", str(baseline),
                    str(bad)]) == 0
    # a NEW finding in the same file still fails (line-number drift is
    # fine — fingerprints key on the source text, not the line)
    bad.write_text("\n\n" + BAD_PRNG
                   + "k2 = jax.random.PRNGKey(1)\n")
    assert rl.main(["--strict", "--baseline", str(baseline),
                    str(bad)]) == 1
    # --no-baseline restores full strictness
    bad.write_text(BAD_PRNG)
    assert rl.main(["--strict", "--baseline", str(baseline),
                    "--no-baseline", str(bad)]) == 1


def test_committed_baseline_is_empty_and_schema_versioned():
    """The committed baseline documents 'zero known findings' — the tree
    must actually scan clean, so the baseline never hides anything."""
    import json as _json
    doc = _json.loads((REPO / "LINT_BASELINE.json").read_text())
    assert doc["version"] == 1
    assert doc["findings"] == []


def test_list_suppressions_reports_rule_file_line(tmp_path):
    f = tmp_path / "sup.py"
    f.write_text("import jax\n"
                 "k = jax.random.PRNGKey(0)  # raftlint: disable=R3\n"
                 "# raftlint: disable-file=C6\n")
    r = _run(["--list-suppressions", str(f)])
    assert r.returncode == 0, r.stderr
    assert "R3" in r.stdout and "sup.py:2" in r.stdout
    assert "C6" in r.stdout and "disable-file" in r.stdout
    assert "2 suppression(s)" in r.stdout


# ---------------------------------------------------------------------------
# SERVING.md threading model: generated-checked against the annotations
# ---------------------------------------------------------------------------

def test_serving_md_lock_hierarchy_matches_declaration():
    from raft_tpu.lint.concurrency import SERVING_LOCK_HIERARCHY
    doc = (REPO / "SERVING.md").read_text()
    expected = " → ".join(f"`{n}`" for n in SERVING_LOCK_HIERARCHY)
    assert expected in doc, (
        "SERVING.md threading-model hierarchy drifted from "
        "lint.concurrency.SERVING_LOCK_HIERARCHY — update the doc line to:"
        f"\n{expected}")


def test_serving_md_lock_table_matches_annotations():
    """The 'which attributes each lock guards' table in SERVING.md is
    generated from the guarded_by annotations + inference; regenerating
    it must reproduce the committed text exactly."""
    from raft_tpu.lint.concurrency import render_threading_table
    doc = (REPO / "SERVING.md").read_text()
    start = doc.index("<!-- lock-table:start -->")
    end = doc.index("<!-- lock-table:end -->")
    committed = doc[start + len("<!-- lock-table:start -->"):end].strip()
    generated = render_threading_table(
        [str(REPO / "raft_tpu" / "serving"),
         str(REPO / "raft_tpu" / "fleet")]).strip()
    assert committed == generated, (
        "SERVING.md lock table drifted from the annotations — replace the "
        "block between the lock-table markers with:\n\n" + generated)


# ---------------------------------------------------------------------------
# the repo itself
# ---------------------------------------------------------------------------

@pytest.mark.lint
def test_self_scan_repo_is_clean():
    findings = scan_paths([str(REPO / "raft_tpu")])
    assert not findings, "\n".join(f.format() for f in findings)


@pytest.mark.lint
def test_self_scan_c_family_runs_and_is_clean():
    """The concurrency family specifically (the strict gate above covers
    it too, but this pins that C1-C6 actually RUN on the tree — a
    regression that unregistered them would otherwise pass silently)."""
    c_rules = [f"C{i}" for i in range(1, 7)]
    findings = scan_paths([str(REPO / "raft_tpu")], select=c_rules)
    assert not findings, "\n".join(f.format() for f in findings)
    assert set(c_rules) <= set(RULES)


@pytest.mark.lint
def test_cli_strict_exits_zero_on_repo_and_one_on_bad_file(tmp_path):
    r = subprocess.run([sys.executable, str(REPO / "tools" / "raftlint.py"),
                        str(REPO / "raft_tpu"), "--strict"],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\nk = jax.random.PRNGKey(0)\n")
    r = subprocess.run([sys.executable, str(REPO / "tools" / "raftlint.py"),
                        str(bad), "--strict"],
                       capture_output=True, text=True)
    assert r.returncode == 1
    assert "R3" in r.stdout
