"""mxlint (tools/mxlint): the AST invariant analyzer.

Per pass: at least one TRUE-POSITIVE fixture (a distilled version of a
bug class this repo actually shipped — the PR-9 double-finish race,
retrace storms, page leaks, hidden host syncs, stat-counter races) and
one CLEAN fixture the pass must stay silent on. Plus waiver and
baseline round-trips, and the lintcore CI contract: the real tree is
clean, and injecting any single fixture bug (one per pass) makes the
gate exit non-zero.

Everything here is pure-AST host work — no jax arrays are built, so
the whole module stays well inside the tier-1 budget.
"""

import json
import os
import textwrap

import pytest

from tools.mxlint import analyze_project, build_project
from tools.mxlint.cli import main as mxlint_main
from tools.mxlint.core import load_baseline, save_baseline
from tools.mxlint.passes import default_passes
from tools.mxlint.passes.host_sync import HostSyncPass

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------- #
# harness
# --------------------------------------------------------------------- #

def _tree(tmp_path, files):
    """Materialize {relpath: source} under tmp_path; returns root."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return str(tmp_path)


def _findings(tmp_path, files, rule=None, passes=None, baseline=None):
    root = _tree(tmp_path, files)
    project = build_project(sorted(files), root)
    out = analyze_project(project, passes or default_passes(),
                          baseline or {})
    if rule is not None:
        out = [f for f in out if f.rule == rule]
    return out


def _active(findings):
    return [f for f in findings
            if f.status == "active" and f.severity == "error"]


# --------------------------------------------------------------------- #
# pass 1: trace-host-leak
# --------------------------------------------------------------------- #

BAD_TRACED = {
    "incubator_mxnet_tpu/ops/badtrace.py": """
        import time
        import numpy as np
        import jax


        def traced(x, y):
            t = time.time()
            f = float(x)
            r = np.random.rand()
            m = np.asarray(y)
            return x * t + f + r + m.sum()


        fast = jax.jit(traced)
    """,
}

CLEAN_TRACED = {
    "incubator_mxnet_tpu/ops/goodtrace.py": """
        import time
        import numpy as np
        import jax
        import jax.numpy as jnp


        def traced(x, key):
            noise = jax.random.normal(key, x.shape)
            return jnp.tanh(x) + noise


        fast = jax.jit(traced)


        def host_helper(v):
            # NOT reachable from any jit site: host casts are fine here
            return float(v) + time.time() + np.random.rand()
    """,
}


def test_trace_pass_flags_host_leaks(tmp_path):
    active = _active(_findings(tmp_path, BAD_TRACED,
                               rule="trace-host-leak"))
    msgs = "\n".join(f.message for f in active)
    assert len(active) >= 4
    assert "host clock" in msgs
    assert "float()" in msgs
    assert "host RNG" in msgs
    assert "np.asarray" in msgs


def test_trace_pass_clean_fixture(tmp_path):
    assert _active(_findings(tmp_path, CLEAN_TRACED,
                             rule="trace-host-leak")) == []


def test_trace_pass_follows_call_graph(tmp_path):
    files = {
        "incubator_mxnet_tpu/ops/chained.py": """
            import jax


            def helper(v):
                return int(v) + 1


            def traced(x):
                return helper(x)


            fast = jax.jit(traced)
        """,
    }
    active = _active(_findings(tmp_path, files, rule="trace-host-leak"))
    assert len(active) == 1 and active[0].symbol == "helper"


def test_trace_pass_decorated_and_method_roots(tmp_path):
    files = {
        "incubator_mxnet_tpu/ops/decorated.py": """
            import functools
            import time
            import jax


            @functools.partial(jax.jit, static_argnames=("k",))
            def decorated(x, k):
                return x * time.monotonic()


            class Engine:
                def __init__(self):
                    self._step = jax.jit(self._step_fn)

                def _step_fn(self, x):
                    return bool(x)
        """,
    }
    active = _active(_findings(tmp_path, files, rule="trace-host-leak"))
    symbols = {f.symbol for f in active}
    assert "decorated" in symbols
    assert "Engine._step_fn" in symbols


def test_trace_pass_follows_a_closure_into_the_model_it_is_handed_to(
        tmp_path):
    """The serving programs' shape: a jitted engine method hands a
    closure to a method of the model it holds. Both bodies run under
    the trace, and so does a function the closure passes on by name;
    ``self.n += 1`` in the program is a store into the object, not a
    rebinding of the ``self`` the closure captures."""
    files = {
        "incubator_mxnet_tpu/models/seam.py": """
            class Model:
                def cached_forward(self, ids, attend):
                    return attend(0, ids) + float(ids)
        """,
        "incubator_mxnet_tpu/serve/seam_engine.py": """
            import jax


            def write(pool, new):
                return pool + int(new)


            class Engine:
                def __init__(self, model):
                    self.model = model
                    self.traces = 0
                    self._step = jax.jit(self._step_fn)

                def _put(self, write_fn, pool, new):
                    return write_fn(pool, new)

                def _step_fn(self, pool, ids):
                    self.traces += 1
                    self.traces += 1

                    def attend(i, q):
                        return self._put(write, pool, q) + bool(q)

                    return self.model.cached_forward(ids, attend)
        """,
    }
    found = _findings(tmp_path, files, rule="trace-host-leak")
    assert {f.symbol for f in _active(found)} == {
        "Model.cached_forward", "Engine._step_fn.attend", "write"}
    assert not [f for f in found if "captures" in f.message]


def test_trace_pass_follows_a_seam_with_two_models_and_keyword_closures(
        tmp_path):
    """The seam's second user: two model classes define the method the
    engine calls on the model it holds, and the closures are handed by
    keyword. Both models' bodies and both closures run under the trace;
    a method name that many classes share is still not followed."""
    files = {
        "incubator_mxnet_tpu/models/seam_a.py": """
            class ModelA:
                def cached_forward(self, ids, attend, state=None):
                    return attend(0, ids) + float(ids)
        """,
        "incubator_mxnet_tpu/models/seam_b.py": """
            class ModelB:
                def cached_forward(self, ids, attend, state=None):
                    return state(0, ids) + int(ids)
        """,
        "incubator_mxnet_tpu/models/many.py": """
            class P:
                def common(self, x):
                    return float(x)

            class Q:
                def common(self, x):
                    return float(x)

            class R:
                def common(self, x):
                    return float(x)

            class S:
                def common(self, x):
                    return float(x)
        """,
        "incubator_mxnet_tpu/serve/seam_engine2.py": """
            import jax


            class Engine:
                def __init__(self, model):
                    self.model = model
                    self._step = jax.jit(self._step_fn)

                def _step_fn(self, pool, ids):
                    def attend(i, q):
                        return pool + bool(q)

                    def state(i, q):
                        return pool + bool(q)

                    self.model.common(ids)
                    return self.model.cached_forward(ids, attend=attend,
                                                     state=state)
        """,
    }
    found = _findings(tmp_path, files, rule="trace-host-leak")
    assert {f.symbol for f in _active(found)} == {
        "ModelA.cached_forward", "ModelB.cached_forward",
        "Engine._step_fn.attend", "Engine._step_fn.state"}


def test_serve_imports_no_private_name_of_models():
    """serve/ asks a model for ``cache_layout`` and ``cached_forward``
    (docs/SERVING.md "What the engine asks of a model") and reads none
    of its insides: no underscore name crosses from models/ into
    serve/, which is what keeps the scheduler from growing back into
    the model."""
    import ast
    serve = os.path.join(REPO_ROOT, "incubator_mxnet_tpu", "serve")
    private = []
    for name in sorted(os.listdir(serve)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(serve, name)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and \
                    "models" in (node.module or "").split("."):
                private += [f"{name}:{node.lineno} {a.name}"
                            for a in node.names if a.name.startswith("_")]
    assert not private, private


# --------------------------------------------------------------------- #
# pass 2: terminal-outcome (the PR-9 double-finish race, distilled)
# --------------------------------------------------------------------- #

BAD_OUTCOME = {
    "incubator_mxnet_tpu/serve/badoutcome.py": """
        class Scheduler:
            def _record_terminal(self, request, outcome):
                request.outcome = outcome
                self.health[outcome.value] += 1

            def evict_expired(self, request, outcome):
                # the double-finish race: a second writer that does not
                # go through the recorder
                request.outcome = outcome

            def fixup_counts(self, outcome):
                self.health[outcome.value] += 1
    """,
}

CLEAN_OUTCOME = {
    "incubator_mxnet_tpu/serve/goodoutcome.py": """
        class Scheduler:
            def __init__(self):
                self.health = {}

            def _record_terminal(self, request, outcome):
                request.outcome = outcome
                self.health[outcome.value] += 1

            def reset_for_requeue(self, request):
                request.outcome = None      # reset, not a terminal

            def evict(self, request, outcome):
                self._record_terminal(request, outcome)
    """,
}


def test_outcome_pass_flags_second_writer(tmp_path):
    active = _active(_findings(tmp_path, BAD_OUTCOME,
                               rule="terminal-outcome"))
    assert {f.symbol for f in active} == \
        {"Scheduler.evict_expired", "Scheduler.fixup_counts"}


def test_outcome_pass_clean_fixture(tmp_path):
    assert _active(_findings(tmp_path, CLEAN_OUTCOME,
                             rule="terminal-outcome")) == []


def test_outcome_pass_scoped_to_serve_and_train(tmp_path):
    files = {"incubator_mxnet_tpu/gluon/other.py": """
        class T:
            def set(self, r, o):
                r.outcome = o
    """}
    assert _active(_findings(tmp_path, files,
                             rule="terminal-outcome")) == []


BAD_EVENT_BUFFER = {
    "incubator_mxnet_tpu/serve/badevents.py": """
        class Engine:
            def sneak_event(self, ev):
                # bypasses FlightRecorder.emit: no seq, no histogram
                # ingestion, no capacity bound — the round-17 event
                # discipline violation, distilled
                self.flight._rings["engine"].append(ev)

            def peek(self):
                return list(self.flight._rings.values())
    """,
}

CLEAN_EVENT_BUFFER = {
    "incubator_mxnet_tpu/serve/goodevents.py": """
        from collections import deque


        class FlightRecorder:
            def __init__(self):
                self._rings = {}

            def emit(self, component, ev):
                ring = self._rings.setdefault(component, deque())
                ring.append(ev)

            def events(self):
                return [e for r in self._rings.values() for e in r]


        class Engine:
            def record(self, ev):
                self.flight.emit("engine", ev)   # the one API
    """,
}


def test_outcome_pass_flags_event_buffer_bypass(tmp_path):
    active = _active(_findings(tmp_path, BAD_EVENT_BUFFER,
                               rule="terminal-outcome"))
    assert {f.symbol for f in active} == \
        {"Engine.sneak_event", "Engine.peek"}
    assert all("FlightRecorder API" in f.message for f in active)


def test_outcome_pass_event_buffer_clean_inside_recorder(tmp_path):
    assert _active(_findings(tmp_path, CLEAN_EVENT_BUFFER,
                             rule="terminal-outcome")) == []


def test_outcome_pass_event_buffer_covers_whole_package(tmp_path):
    """The ring-discipline sub-rule is scoped to the whole package —
    checkpoint/manager.py holds a recorder too, so a bypass there must
    be caught even though the outcome/health checks stay scoped to
    serve/+train/."""
    files = {"incubator_mxnet_tpu/checkpoint/badckpt.py": """
        class Manager:
            def sneak(self, ev):
                self.flight._rings["checkpoint"].append(ev)
    """}
    active = _active(_findings(tmp_path, files,
                               rule="terminal-outcome"))
    assert {f.symbol for f in active} == {"Manager.sneak"}


# --------------------------------------------------------------------- #
# pass 3: page-refcount
# --------------------------------------------------------------------- #

BAD_PAGES = {
    "incubator_mxnet_tpu/serve/badpages.py": """
        class LeakyIndex:
            def retain(self, pages):
                for p in pages:
                    self._alloc.incref(p)

            def grab_one(self):
                return self._alloc.alloc()
    """,
}

CLEAN_PAGES = {
    "incubator_mxnet_tpu/serve/goodpages.py": """
        class PairedIndex:
            def retain(self, pages):
                for p in pages:
                    self._alloc.incref(p)

            def drop(self, pages):
                for p in pages:
                    self._alloc.decref(p)
    """,
}


def test_page_pass_flags_unpaired_acquire(tmp_path):
    active = _active(_findings(tmp_path, BAD_PAGES,
                               rule="page-refcount"))
    assert len(active) == 2
    assert all("silent pool leak" in f.message for f in active)


def test_page_pass_clean_fixture(tmp_path):
    assert _active(_findings(tmp_path, CLEAN_PAGES,
                             rule="page-refcount")) == []


BAD_TIER = {
    "incubator_mxnet_tpu/serve/badtier.py": """
        class Sidecar:
            def peek(self, store):
                return len(store._entries)

            def shrink(self, store):
                store._dram_used -= 4096


        class KVTierStore:
            def promote(self, key, ent):
                # a demoted page has no refcount: the store must not
                # hand out (or free) HBM pages itself
                page = self._alloc.alloc()
                self._alloc.free(page)
                return page
    """,
}

CLEAN_TIER = {
    "incubator_mxnet_tpu/serve/goodtier.py": """
        class KVTierStore:
            def __init__(self):
                self._entries = {}
                self._dram_used = 0

            def entries(self):
                for key, bucket in self._entries.items():
                    for ent in bucket:
                        yield key, ent


        class Sidecar:
            def peek(self, store):
                return sum(1 for _ in store.entries())
    """,
}


def test_page_pass_tier_internals_and_alloc_in_store(tmp_path):
    active = _active(_findings(tmp_path, BAD_TIER,
                               rule="page-refcount"))
    msgs = "\n".join(f.message for f in active)
    assert msgs.count("outside KVTierStore") == 2
    assert msgs.count("inside KVTierStore") == 2
    # the unpaired-alloc check must NOT double-fire here: alloc and
    # free are paired inside the class scope
    assert "silent pool leak" not in msgs


def test_page_pass_tier_clean_fixture(tmp_path):
    assert _active(_findings(tmp_path, CLEAN_TIER,
                             rule="page-refcount")) == []


BAD_TRANSPORT = {
    "incubator_mxnet_tpu/serve/badtransport.py": """
        class ChainForger:
            def splice(self, capsule, payload):
                # forges a record past verify(): the wire chain the
                # destination trusts no longer covers this payload
                capsule._records.append(payload)
                capsule._chain_crc = 0

        class Sidecar:
            def steal_pages(self, engine, rid):
                # moves custody pages around the engine's
                # detach/release seam: audit_pages can no longer
                # prove free XOR live XOR demoted XOR in-capsule
                pages = engine._capsule_pages.pop(rid)
                return pages
    """,
}

CLEAN_TRANSPORT = {
    "incubator_mxnet_tpu/serve/goodtransport.py": """
        class PageCapsule:
            def __init__(self):
                self._records = []
                self._chain_crc = 0

            def payloads(self):
                return list(self._records)

        class PageTransport:
            def nbytes(self, capsule):
                return sum(len(r) for r in capsule._records)

        class Sidecar:
            def ship(self, capsule, engine, rid):
                payloads = capsule.payloads()   # the one read API
                engine.release_capsule(rid)     # the one custody API
                return payloads
    """,
}


def test_page_pass_transport_internals(tmp_path):
    active = _active(_findings(tmp_path, BAD_TRANSPORT,
                               rule="page-refcount"))
    msgs = "\n".join(f.message for f in active)
    assert msgs.count("outside PageCapsule/PageTransport") == 2
    assert msgs.count("outside InferenceEngine") == 1


def test_page_pass_transport_clean_fixture(tmp_path):
    assert _active(_findings(tmp_path, CLEAN_TRANSPORT,
                             rule="page-refcount")) == []


def test_page_pass_null_page_and_rc_internals(tmp_path):
    files = {"incubator_mxnet_tpu/serve/nullpage.py": """
        NULL_PAGE = 0


        class Evil:
            def release(self):
                self._alloc.decref(0)
                self._alloc.free(NULL_PAGE)

            def poke(self):
                self._rc[3] += 1
    """}
    active = _active(_findings(tmp_path, files, rule="page-refcount"))
    msgs = "\n".join(f.message for f in active)
    assert msgs.count("null page") == 2
    assert "outside PageAllocator" in msgs


# --------------------------------------------------------------------- #
# pass 4: host-sync
# --------------------------------------------------------------------- #

BAD_HOTLOOP = {
    "incubator_mxnet_tpu/serve/hotloop.py": """
        import jax
        import numpy as np


        class MiniEngine:
            def __init__(self):
                self._decode = jax.jit(lambda x: x + 1)

            def step(self):
                out = self._decode(self.state)
                tok = int(np.asarray(out))       # designed sync
                extra = out.item()               # hidden second sync
                if out > 0:                      # hidden implicit bool
                    tok += 1
                return tok + extra
    """,
}

_HOT = {"incubator_mxnet_tpu/serve/hotloop.py": {"step"}}


def _hot_passes():
    return [HostSyncPass(hot_seeds=_HOT)]


def test_host_sync_flags_hidden_syncs(tmp_path):
    active = _active(_findings(tmp_path, BAD_HOTLOOP, rule="host-sync",
                               passes=_hot_passes()))
    msgs = "\n".join(f.message for f in active)
    assert "np.asarray" in msgs
    assert ".item()" in msgs
    assert "implicit `bool()`" in msgs


def test_host_sync_untaints_after_cast_and_ignores_is_none(tmp_path):
    files = {"incubator_mxnet_tpu/serve/hotloop.py": """
        import jax
        import numpy as np


        class MiniEngine:
            def __init__(self):
                self._decode = jax.jit(lambda x: x + 1)

            def step(self):
                out = self._decode(self.state)
                if out is None:                # identity: NOT a sync
                    return 0
                # mxlint: allow-host-sync(the one designed readback)
                out = np.asarray(out)
                if out > 0:                    # host np array now: free
                    return 1
                return int(out)                # host int now: free
    """}
    findings = _findings(tmp_path, files, rule="host-sync",
                         passes=_hot_passes())
    assert _active(findings) == []
    assert [f.status for f in findings] == ["waived"]


def test_host_sync_taints_through_jit_dicts_and_returns(tmp_path):
    files = {"incubator_mxnet_tpu/serve/hotloop.py": """
        import jax
        import numpy as np


        class MiniEngine:
            def __init__(self):
                self._jits = {}

            def _get_fn(self, sig):
                fn = self._jits.get(sig)
                if fn is None:
                    fn = jax.jit(lambda x: x)
                    self._jits[sig] = fn
                return fn(sig)

            def step(self):
                flag = self._get_fn(8)
                return bool(np.asarray(flag) > 0)
    """}
    active = _active(_findings(tmp_path, files, rule="host-sync",
                               passes=_hot_passes()))
    assert len(active) == 1
    assert "np.asarray" in active[0].message


# --------------------------------------------------------------------- #
# pass 5: lock-discipline
# --------------------------------------------------------------------- #

BAD_LOCKS = {
    "incubator_mxnet_tpu/checkpoint/badlocks.py": """
        import threading


        class RacyWriter:
            def __init__(self):
                self._lock = threading.Lock()
                self.commits = 0
                self._thread = threading.Thread(target=self._loop)

            def _loop(self):
                while True:
                    self.commits += 1     # writer thread, no lock

            def reset(self):
                self.commits = 0          # main path, no lock
    """,
}

CLEAN_LOCKS = {
    "incubator_mxnet_tpu/checkpoint/goodlocks.py": """
        import threading


        class GuardedWriter:
            def __init__(self):
                self._lock = threading.Lock()
                self.commits = 0
                self._thread = threading.Thread(target=self._loop)

            def _loop(self):
                while True:
                    with self._lock:
                        self.commits += 1

            def reset(self):
                with self._lock:
                    self.commits = 0
    """,
}


def test_lock_pass_flags_unguarded_shared_writes(tmp_path):
    active = _active(_findings(tmp_path, BAD_LOCKS,
                               rule="lock-discipline"))
    assert {f.symbol for f in active} == \
        {"RacyWriter._loop", "RacyWriter.reset"}


def test_lock_pass_clean_fixture(tmp_path):
    assert _active(_findings(tmp_path, CLEAN_LOCKS,
                             rule="lock-discipline")) == []


def test_lock_pass_flags_lockless_thread_class(tmp_path):
    files = {"incubator_mxnet_tpu/io/lockless.py": """
        import threading


        class NoLock:
            def __init__(self):
                self._thread = threading.Thread(target=self._loop)

            def _loop(self):
                self.n = 1
    """}
    active = _active(_findings(tmp_path, files, rule="lock-discipline"))
    assert len(active) == 1
    assert "designates no lock" in active[0].message


# --------------------------------------------------------------------- #
# waivers
# --------------------------------------------------------------------- #

def test_waiver_suppresses_and_records_reason(tmp_path):
    files = {"incubator_mxnet_tpu/serve/waived.py": """
        class Scheduler:
            def evict(self, request, outcome):
                # mxlint: allow-terminal-outcome(distilled fixture, not a real writer)
                request.outcome = outcome
    """}
    findings = _findings(tmp_path, files, rule="terminal-outcome")
    assert len(findings) == 1
    assert findings[0].status == "waived"
    assert "distilled fixture" in findings[0].reason


def test_scope_level_waiver_on_def_line(tmp_path):
    files = {"incubator_mxnet_tpu/serve/scoped.py": """
        class Scheduler:
            # mxlint: allow-terminal-outcome(whole-method waiver: legacy shim)
            def evict(self, request, outcome):
                request.outcome = outcome
    """}
    findings = _findings(tmp_path, files, rule="terminal-outcome")
    assert [f.status for f in findings] == ["waived"]


def test_waiver_without_reason_is_a_finding(tmp_path):
    files = {"incubator_mxnet_tpu/serve/noreason.py": """
        X = 1  # mxlint: allow-terminal-outcome()
    """}
    findings = _findings(tmp_path, files, rule="waiver-syntax")
    assert len(findings) == 1
    assert "no reason" in findings[0].message


def test_waiver_unknown_rule_is_a_finding(tmp_path):
    files = {"incubator_mxnet_tpu/serve/unknown.py": """
        X = 1  # mxlint: allow-made-up-rule(sounds legit)
    """}
    findings = _findings(tmp_path, files, rule="waiver-syntax")
    assert len(findings) == 1
    assert "unknown rule" in findings[0].message


def test_first_body_line_waiver_is_not_scope_wide(tmp_path):
    """Review regression: a LINE waiver on (or above) a function's
    first statement must not silently become a whole-function waiver —
    the later unwaived violation stays active (fail-closed)."""
    files = {"incubator_mxnet_tpu/serve/firstline.py": """
        class Scheduler:
            def evict(self, request, other):
                # mxlint: allow-terminal-outcome(this one write only)
                request.outcome = 1
                other.outcome = 2
    """}
    findings = _findings(tmp_path, files, rule="terminal-outcome")
    assert sorted(f.status for f in findings) == ["active", "waived"]
    active = _active(findings)[0]
    assert "other" in tmp_path.joinpath(
        "incubator_mxnet_tpu/serve/firstline.py").read_text() \
        .splitlines()[active.line - 1]


def test_host_sync_item_on_host_value_not_flagged(tmp_path):
    """Review regression: `.item()` on a pure-host numpy value is not
    a device sync and must not demand a waiver."""
    files = {"incubator_mxnet_tpu/serve/hotloop.py": """
        import numpy as np


        class MiniEngine:
            def step(self):
                host = np.zeros(3)
                return host.max().item()
    """}
    assert _active(_findings(tmp_path, files, rule="host-sync",
                             passes=_hot_passes())) == []


def test_aliased_baseline_group_carries_attribution_note(tmp_path):
    """Review regression: when identical findings split between
    baselined and active, the active one's report admits the line
    attribution is order-based instead of silently pointing at an
    arbitrary line."""
    first = _findings(tmp_path, BAD_OUTCOME, rule="terminal-outcome")
    dup = [f for f in first if f.symbol == "Scheduler.evict_expired"]
    baseline = {dup[0].key: "acknowledged debt"}
    src = textwrap.dedent(
        BAD_OUTCOME["incubator_mxnet_tpu/serve/badoutcome.py"])
    marker = "recorder\n        request.outcome = outcome"
    assert marker in src
    doubled = {
        "incubator_mxnet_tpu/serve/badoutcome.py": src.replace(
            marker, marker + "\n        request.outcome = outcome")}
    findings = [
        f for f in _findings(tmp_path / "d", doubled,
                             rule="terminal-outcome", baseline=baseline)
        if f.symbol == "Scheduler.evict_expired"]
    assert sorted(f.status for f in findings) == ["active", "baselined"]
    active = [f for f in findings if f.status == "active"][0]
    assert "re-triage the whole group" in active.note
    assert "re-triage" in active.render()


def test_docstring_mention_is_not_a_waiver(tmp_path):
    files = {"incubator_mxnet_tpu/serve/docmention.py": '''
        """Docs may say # mxlint: allow-terminal-outcome(reason) freely."""
        X = 1
    '''}
    assert _findings(tmp_path, files, rule="waiver-syntax") == []


# --------------------------------------------------------------------- #
# baseline
# --------------------------------------------------------------------- #

def test_baseline_roundtrip(tmp_path):
    findings = _findings(tmp_path, BAD_OUTCOME, rule="terminal-outcome")
    keys = {f.key: "pre-existing: tracked as debt" for f in findings}
    bl_path = str(tmp_path / "bl.json")
    save_baseline(bl_path, keys)
    loaded = load_baseline(bl_path)
    assert loaded == keys

    again = _findings(tmp_path, BAD_OUTCOME, rule="terminal-outcome",
                      baseline=loaded)
    assert _active(again) == []
    assert all(f.status == "baselined" and "debt" in f.reason
               for f in again)


def test_baseline_key_survives_line_shift(tmp_path):
    first = _findings(tmp_path, BAD_OUTCOME, rule="terminal-outcome")
    shifted = {
        "incubator_mxnet_tpu/serve/badoutcome.py":
            "# a new comment line at the top\n# another\n" +
            textwrap.dedent(
                BAD_OUTCOME["incubator_mxnet_tpu/serve/badoutcome.py"])}
    second = _findings(tmp_path / "b", shifted, rule="terminal-outcome")
    assert {f.key for f in first} == {f.key for f in second}
    assert [f.line for f in first] != [f.line for f in second]


def test_cli_update_baseline_then_clean(tmp_path):
    root = _tree(tmp_path, BAD_OUTCOME)
    bl = "bl.json"
    rc = mxlint_main(["--root", root, "--baseline", bl,
                      "incubator_mxnet_tpu"])
    assert rc == 1
    rc = mxlint_main(["--root", root, "--baseline", bl,
                      "--update-baseline", "incubator_mxnet_tpu"])
    assert rc == 0
    data = json.loads((tmp_path / bl).read_text())
    assert data["entries"] and all(e["reason"] for e in data["entries"])
    rc = mxlint_main(["--root", root, "--baseline", bl,
                      "incubator_mxnet_tpu"])
    assert rc == 0


# --------------------------------------------------------------------- #
# the lintcore CI contract
# --------------------------------------------------------------------- #

def test_lintcore_real_tree_is_clean():
    """`ci/run.sh lintcore` equivalence: the checked-in tree plus the
    checked-in baseline must have zero unbaselined findings."""
    rc = mxlint_main(["--root", REPO_ROOT,
                      "--baseline", "ci/mxlint_baseline.json"])
    assert rc == 0


_INJECTIONS = {
    # one representative bug per pass, injected as a fresh file at a
    # path inside the pass's scope (host-sync: a step() on the real
    # hot-module path so the default HOT_SEEDS pick it up)
    "trace-host-leak": (
        "incubator_mxnet_tpu/ops/injected_trace.py",
        BAD_TRACED["incubator_mxnet_tpu/ops/badtrace.py"]),
    "terminal-outcome": (
        "incubator_mxnet_tpu/serve/injected_outcome.py",
        BAD_OUTCOME["incubator_mxnet_tpu/serve/badoutcome.py"]),
    # second terminal-outcome injection: the round-17 event-buffer
    # rule ("#" suffix = parametrize id only; the rule is the prefix)
    "terminal-outcome#events": (
        "incubator_mxnet_tpu/serve/injected_events.py",
        BAD_EVENT_BUFFER["incubator_mxnet_tpu/serve/badevents.py"]),
    "page-refcount": (
        "incubator_mxnet_tpu/serve/injected_pages.py",
        BAD_PAGES["incubator_mxnet_tpu/serve/badpages.py"]),
    # second page-refcount injection: the round-19 tier rules (a
    # sidecar poking demoted-page bookkeeping + a tier store that
    # allocs/frees HBM pages)
    "page-refcount#tiers": (
        "incubator_mxnet_tpu/serve/injected_tier.py",
        BAD_TIER["incubator_mxnet_tpu/serve/badtier.py"]),
    # third page-refcount injection: the round-20 transport rules (a
    # crc-chain forger + a sidecar moving in-capsule custody pages
    # around detach_slot/release_capsule)
    "page-refcount#transport": (
        "incubator_mxnet_tpu/serve/injected_transport.py",
        BAD_TRANSPORT["incubator_mxnet_tpu/serve/badtransport.py"]),
    "host-sync": (
        "incubator_mxnet_tpu/serve/router.py",
        """
        import jax
        import numpy as np


        class Router:
            def __init__(self):
                self._probe = jax.jit(lambda x: x)

            def _dispatch(self):
                score = self._probe(3)
                return float(np.asarray(score))
        """),
    "lock-discipline": (
        "incubator_mxnet_tpu/checkpoint/injected_locks.py",
        BAD_LOCKS["incubator_mxnet_tpu/checkpoint/badlocks.py"]),
}


@pytest.mark.parametrize("rule", sorted(_INJECTIONS))
def test_lintcore_fails_on_injected_bug(tmp_path, rule):
    """Injecting any SINGLE fixture bug (one per pass) into an
    otherwise-clean tree must flip the lintcore gate non-zero."""
    rel, src = _INJECTIONS[rule]
    rule = rule.split("#")[0]            # "#suffix" = parametrize id
    root = _tree(tmp_path, {rel: src})
    rc = mxlint_main(["--root", root, "incubator_mxnet_tpu"])
    assert rc == 1, f"{rule}: injected bug not caught"
    # and the finding is attributed to the right rule
    findings = _findings(tmp_path / "chk", {rel: src}, rule=rule)
    assert _active(findings), f"{rule}: no active finding for its rule"


def test_parse_error_is_a_finding(tmp_path):
    files = {"incubator_mxnet_tpu/serve/broken.py": "def oops(:\n"}
    findings = _findings(tmp_path, files, rule="parse-error")
    assert len(findings) == 1
