"""tools/lint — the invariant-aware static analysis suite (PR 11).

Three layers:

- per-checker FIXTURE tests: each of PT001-PT007 fires on a seeded
  violation and stays quiet on the blessed idiom (the checker's
  contract, independent of the live tree);
- engine tests: fingerprint stability under line drift, annotation
  parsing, baseline load/validation/round-trip;
- the TIER-1 GATE: the full suite over ``paddle_tpu/`` reports zero
  unbaselined findings against the checked-in baseline — the "no NEW
  violations" CI bar.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.lint import (BaselineError, apply_baseline,        # noqa: E402
                        default_baseline_path, generate_baseline,
                        lint_paths, lint_source, load_baseline,
                        write_baseline)


def ids(findings, checker=None):
    return [f.checker for f in findings
            if checker is None or f.checker == checker]


def only(findings, checker):
    return [f for f in findings if f.checker == checker]


# ---------------------------------------------------------------------------
# PT001 — recompile hazard
# ---------------------------------------------------------------------------
class TestPT001:
    def test_fires_on_jit_per_call(self):
        src = (
            "import jax\n"
            "class M:\n"
            "    def step(self, x):\n"
            "        fn = jax.jit(lambda a: a + 1)\n"
            "        return fn(x)\n")
        f = only(lint_source(src), "PT001")
        assert len(f) == 1 and f[0].line == 4
        assert "fresh trace cache" in f[0].message

    def test_fires_on_immediate_call(self):
        src = ("import jax\n"
               "def probe(x):\n"
               "    return jax.jit(lambda a: a * 2)(x)\n")
        f = only(lint_source(src), "PT001")
        assert len(f) == 1 and "immediately called" in f[0].message

    def test_fires_in_loop_and_on_decorated_local_def(self):
        src = (
            "import jax\n"
            "def run(xs):\n"
            "    outs = []\n"
            "    for x in xs:\n"
            "        fn = jax.jit(lambda a: a)\n"
            "        outs.append(fn(x))\n"
            "    @jax.jit\n"
            "    def inner(a):\n"
            "        return a\n"
            "    return outs, inner\n")
        f = only(lint_source(src), "PT001")
        assert len(f) == 2
        assert any("inside a loop" in x.message for x in f)
        assert any("re-jitted every call" in x.message for x in f)

    def test_fires_on_static_hint_param_without_static_argnames(self):
        src = (
            "import jax\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        def seg(state, n_steps):\n"
            "            return state\n"
            "        self._seg = jax.jit(seg)\n")
        f = only(lint_source(src), "PT001")
        assert len(f) == 1 and "static_argnames" in f[0].message

    def test_quiet_on_blessed_idioms(self):
        src = (
            "import jax, functools\n"
            "from .. import monitor\n"
            "JITTED = jax.jit(lambda a: a)\n"           # module level
            "@functools.partial(jax.jit, static_argnames=('eps',))\n"
            "def k(x, eps):\n"
            "    return x\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = monitor.monitored_jit(lambda a: a)\n"
            "        self._cache = {}\n"
            "        self._lazy = None\n"
            "    def _decode_fn(self, n_steps):\n"
            "        if n_steps not in self._cache:\n"
            "            def seg(s, n_steps):\n"
            "                return s\n"
            "            self._cache[n_steps] = jax.jit(\n"
            "                seg, static_argnames=('n_steps',))\n"
            "        return self._cache[n_steps]\n"
            "    def fn(self):\n"
            "        if self._lazy is None:\n"
            "            self._lazy = jax.jit(lambda a: a)\n"
            "        return self._lazy\n"
            "def build(f):\n"
            "    return jax.jit(f)\n")
        assert only(lint_source(src), "PT001") == []

    def test_keyed_cache_blesses_static_hint(self):
        src = (
            "import jax\n"
            "class E:\n"
            "    def seg_fn(self, n_steps):\n"
            "        def seg(s, n_steps):\n"
            "            return s\n"
            "        self._c[n_steps] = jax.jit(seg)\n"
            "        return self._c[n_steps]\n")
        assert only(lint_source(src), "PT001") == []

    def test_escape_needs_reason(self):
        base = ("import jax\n"
                "def probe(x):\n"
                "    {esc}\n"
                "    return jax.jit(lambda a: a)(x)\n")
        bad = lint_source(base.format(esc="# lint: allow-recompile"))
        assert any("requires a reason" in f.message
                   for f in only(bad, "PT001"))
        good = lint_source(base.format(
            esc="# lint: allow-recompile(one-shot probe)"))
        assert only(good, "PT001") == []


# ---------------------------------------------------------------------------
# PT002 — host sync in hot path
# ---------------------------------------------------------------------------
class TestPT002:
    HOT = (
        "import numpy as np\n"
        "class S:\n"
        "    def _gap(self):  # lint: hot-path\n"
        "        toks = np.asarray(self.toks_dev)\n"
        "        v = self.x.item()\n"
        "        n = int(self.lens[0])\n"
        "        self._helper()\n"
        "    def _helper(self):\n"
        "        import jax\n"
        "        jax.device_get(self.y)\n"
        "    def cold(self):\n"
        "        return np.asarray(self.toks_dev)\n")

    def test_fires_in_hot_and_transitively_not_in_cold(self):
        f = only(lint_source(self.HOT), "PT002")
        details = sorted(x.detail for x in f)
        assert details == [".item()", "int()", "jax.device_get",
                           "np.asarray"]
        # the reached-from context names the root
        helper = [x for x in f if x.context == "S._helper"][0]
        assert "reached from S._gap" in helper.message
        assert all(x.context != "S.cold" for x in f)

    def test_quiet_without_annotation(self):
        src = self.HOT.replace("  # lint: hot-path", "")
        assert only(lint_source(src), "PT002") == []

    def test_escape_hatch_requires_reason(self):
        src = (
            "import numpy as np\n"
            "class S:\n"
            "    def _gap(self):  # lint: hot-path\n"
            "        # lint: allow-host-sync(collection readback)\n"
            "        toks = np.asarray(self.toks_dev)\n"
            "        done = np.asarray(self.done_dev)  "
            "# lint: allow-host-sync\n")
        f = only(lint_source(src), "PT002")
        assert len(f) == 1 and "REASON is required" in f[0].message

    def test_escape_covers_multiline_statement(self):
        src = (
            "import numpy as np\n"
            "class S:\n"
            "    def _gap(self):  # lint: hot-path\n"
            "        # lint: allow-host-sync(host-list copy)\n"
            "        ids = np.concatenate(\n"
            "            [self.a,\n"
            "             np.asarray(self.b, np.int32)])\n")
        assert only(lint_source(src), "PT002") == []

    def test_host_to_device_not_flagged(self):
        src = (
            "import jax.numpy as jnp\n"
            "class S:\n"
            "    def _gap(self):  # lint: hot-path\n"
            "        x = jnp.asarray([1, 2])\n"
            "        busy = bool(self._active or self._adm)\n"
            "        n = int(local_host_array[0])\n")
        assert only(lint_source(src), "PT002") == []


# ---------------------------------------------------------------------------
# PT003 — series lifecycle
# ---------------------------------------------------------------------------
class TestPT003:
    def test_fires_without_retirement(self):
        src = (
            "from .. import monitor\n"
            "class Pool:\n"
            "    def _pages(self):\n"
            "        return monitor.gauge('x_pages', 'h', ('pool',))\n"
            "    def close(self):\n"
            "        pass\n")
        f = only(lint_source(src), "PT003")
        assert len(f) == 1 and f[0].detail == "x_pages"
        assert "never retired" in f[0].message

    def test_fires_without_any_retirement_root(self):
        src = ("from .. import monitor\n"
               "class Pool:\n"
               "    def _pages(self):\n"
               "        return monitor.gauge('x_pages', 'h', ('pool',))\n")
        assert len(only(lint_source(src), "PT003")) == 1

    def test_fires_outside_class(self):
        src = ("from .. import monitor\n"
               "G = monitor.gauge('x_depth', 'h', ('loader',))\n")
        f = only(lint_source(src), "PT003")
        assert len(f) == 1 and "outside a class" in f[0].message

    def test_quiet_on_name_tuple_remove_series_idiom(self):
        src = (
            "from .. import monitor\n"
            "class Srv:\n"
            "    def _req(self):\n"
            "        return monitor.counter('x_req', 'h',\n"
            "                               ('server', 'event'))\n"
            "    def shutdown(self):\n"
            "        for name in ('x_req',):\n"
            "            monitor.remove_series(name, server=self.lbl)\n")
        assert only(lint_source(src), "PT003") == []

    def test_quiet_on_helper_remove_idiom_via_close_chain(self):
        src = (
            "from .. import monitor\n"
            "class Pool:\n"
            "    def _pages(self):\n"
            "        return monitor.gauge('x_pages', 'h', ('pool',))\n"
            "    def close(self):\n"
            "        self._retire_all()\n"
            "    def _retire_all(self):\n"
            "        self._pages().remove(pool=self.lbl)\n")
        assert only(lint_source(src), "PT003") == []

    def test_retires_series_annotation_and_base_class_root(self):
        src = (
            "from .. import monitor\n"
            "class Base:\n"
            "    def close(self):\n"
            "        monitor.remove_series('x_tps', engine=self.lbl)\n"
            "class Eng(Base):\n"
            "    def _tps(self):\n"
            "        return monitor.gauge('x_tps', 'h', ('engine',))\n"
            "class Cb:\n"
            "    def _fit(self):\n"
            "        return monitor.gauge('x_fit', 'h', ('fit',))\n"
            "    # lint: retires-series\n"
            "    def on_train_end(self):\n"
            "        self._fit().remove(fit=self.lbl)\n")
        assert only(lint_source(src), "PT003") == []

    def test_non_instance_labels_ignored(self):
        src = ("from .. import monitor\n"
               "C = monitor.counter('x_total', 'h', ('event',))\n")
        assert only(lint_source(src), "PT003") == []


# ---------------------------------------------------------------------------
# PT004 — lock discipline
# ---------------------------------------------------------------------------
class TestPT004:
    SRC = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._flag = False     # guarded-by: self._lock\n"
        "        self._free = []        # guarded-by: scheduler-thread\n"
        "    def good(self):\n"
        "        with self._lock:\n"
        "            self._flag = True\n"
        "    def bad(self):\n"
        "        return self._flag\n"
        "    def owned(self):\n"
        "        return len(self._free)\n")

    def test_fires_outside_lock_only(self):
        f = only(lint_source(self.SRC), "PT004")
        assert len(f) == 1
        assert f[0].context == "S.bad" and f[0].detail == "_flag"

    def test_thread_ownership_form_not_enforced(self):
        f = only(lint_source(self.SRC), "PT004")
        assert all(x.detail != "_free" for x in f)

    def test_escape_hatch(self):
        src = self.SRC.replace(
            "        return self._flag",
            "        # lint: allow-unlocked(atomic read)\n"
            "        return self._flag")
        assert only(lint_source(src), "PT004") == []

    def test_missing_lock_declaration_is_config_error(self):
        src = (
            "class S:\n"
            "    def __init__(self):\n"
            "        self._flag = False  # guarded-by: self._nope\n"
            "    def read(self):\n"
            "        return self._flag\n")
        f = only(lint_source(src), "PT004")
        assert len(f) == 1 and "never creates" in f[0].message


# ---------------------------------------------------------------------------
# PT005 — flag gating
# ---------------------------------------------------------------------------
class TestPT005:
    def test_fires_on_ungated_trace_and_monitor_calls(self):
        src = (
            "from .. import monitor\n"
            "from .. import tracing as trace\n"
            "class S:\n"
            "    def seam(self):\n"
            "        trace.event('queue.enqueue', rid=3)\n"
            "        self._req().labels(server=self.lbl).inc()\n"
            "        monitor.histogram('x_s', 'h').observe(0.1)\n")
        f = only(lint_source(src), "PT005")
        assert len(f) == 3

    def test_quiet_when_gated(self):
        src = (
            "from .. import monitor\n"
            "from .. import tracing as trace\n"
            "class S:\n"
            "    def seam(self):\n"
            "        if trace.enabled():\n"
            "            trace.event('queue.enqueue', rid=3)\n"
            "        if monitor.enabled():\n"
            "            self._req().labels(server=self.lbl).inc()\n"
            "    def early(self):\n"
            "        if not monitor.enabled():\n"
            "            return\n"
            "        monitor.histogram('x_s', 'h').observe(0.1)\n"
            "    def not_metrics(self):\n"
            "        self._wake.set()\n"          # threading.Event, ok
            "        self.arr.at[0].set(1)\n")    # jax .at update, ok
        assert only(lint_source(src), "PT005") == []

    def test_internal_ring_and_store_rules(self):
        src = (
            "_enabled = False\n"
            "def event(phase):\n"
            "    _ring.append((phase,))\n"
            "def gated_event(phase):\n"
            "    if not _enabled:\n"
            "        return\n"
            "    _ring.append((phase,))\n"
            "class Counter:\n"
            "    def _inc(self, key, amount):\n"
            "        self._values[key] = amount\n"
            "    def _inc_gated(self, key, amount):\n"
            "        if not _enabled:\n"
            "            return\n"
            "        self._values[key] = amount\n")
        f = only(lint_source(src, filename="paddle_tpu/tracing/x.py"),
                 "PT005")
        assert sorted(x.detail for x in f) == ["ring-append",
                                               "values-store"]
        # outside the observability packages the internal rules are off
        assert only(lint_source(src, filename="paddle_tpu/io/x.py"),
                    "PT005") == []

    def test_escape_hatch(self):
        src = (
            "from .. import tracing as trace\n"
            "def seam():\n"
            "    # lint: allow-ungated(cold admin path, never hot)\n"
            "    trace.event('configured')\n")
        assert only(lint_source(src), "PT005") == []


# ---------------------------------------------------------------------------
# PT006 — blocking socket I/O in a hot path
# ---------------------------------------------------------------------------
class TestPT006:
    HOT = (
        "from urllib.request import urlopen\n"
        "import http.client\n"
        "class R:\n"
        "    def status(self):  # lint: hot-path\n"
        "        r = urlopen(self.url)\n"
        "        return self._poll()\n"
        "    def _poll(self):\n"
        "        conn = http.client.HTTPConnection(self.host)\n"
        "        conn.request('GET', '/healthz')\n"
        "        return conn.getresponse()\n"
        "    def cold(self):\n"
        "        return urlopen(self.url)\n")

    def test_fires_in_hot_and_transitively_not_in_cold(self):
        f = only(lint_source(self.HOT), "PT006")
        details = sorted(x.detail for x in f)
        assert details == [".getresponse()", "HTTPConnection",
                           "urlopen"]
        poll = [x for x in f if x.context == "R._poll"]
        assert poll and all("reached from R.status" in x.message
                            for x in poll)
        assert all(x.context != "R.cold" for x in f)

    def test_quiet_without_annotation(self):
        src = self.HOT.replace("  # lint: hot-path", "")
        assert only(lint_source(src), "PT006") == []

    def test_bounded_timeout_quiets_constructors_not_reads(self):
        src = (
            "from urllib.request import urlopen\n"
            "import socket\n"
            "class R:\n"
            "    def load(self):  # lint: hot-path\n"
            "        r = urlopen(self.url, timeout=2.0)\n"
            "        c = socket.create_connection(self.addr,\n"
            "                                     timeout=self.t)\n"
            "        return c.recv(4096)\n")
        f = only(lint_source(src), "PT006")
        # the timeout-bounded opener/constructor are fine; the raw
        # recv has no per-call bound and still needs the escape hatch
        assert [x.detail for x in f] == [".recv()"]

    def test_explicit_timeout_none_still_fires(self):
        src = (
            "from urllib.request import urlopen\n"
            "class R:\n"
            "    def load(self):  # lint: hot-path\n"
            "        return urlopen(self.url, timeout=None)\n")
        f = only(lint_source(src), "PT006")
        assert [x.detail for x in f] == ["urlopen"]

    def test_escape_hatch_requires_reason(self):
        src = (
            "class R:\n"
            "    def load(self):  # lint: hot-path\n"
            "        # lint: allow-blocking-io(reader thread's whole "
            "job is this wait)\n"
            "        a = self.sock.recv(4096)\n"
            "        b = self.sock.recv(4096)  # lint: allow-blocking-io\n")
        f = only(lint_source(src), "PT006")
        assert len(f) == 1 and "REASON is required" in f[0].message


# ---------------------------------------------------------------------------
# engine: annotations, fingerprints, baseline
# ---------------------------------------------------------------------------
# ---------------------------------------------------------------------------
# PT007 — eager device computation in hot path
# ---------------------------------------------------------------------------
class TestPT007:
    HOT = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "class E:\n"
        "    def segment(self, seed):  # lint: hot-path\n"
        "        key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)\n"
        "        pos = jnp.int32(3)\n"
        "        table = jnp.asarray(self.table)\n"
        "        self._retire(0)\n"
        "        return self._fn()(key, pos, table, np.int32(4))\n"
        "    def _retire(self, slot):\n"
        "        self.active = self.active.at[slot].set(False)\n"
        "    def _fn(self):\n"
        "        def program(key, pos, table, n):\n"
        "            key, sub = jax.random.split(key)\n"
        "            return jnp.where(table > pos, n, 0).at[0].set(1)\n"
        "        return jax.jit(program)\n"
        "    def cold(self):\n"
        "        return jnp.zeros((4,)).at[0].set(1)\n")

    def test_fires_in_hot_and_transitively_not_in_cold(self):
        f = only(lint_source(self.HOT), "PT007")
        assert sorted(x.detail for x in f) == [
            ".at[]", "jax.random.PRNGKey", "jax.random.fold_in",
            "jnp.int32"]
        retire = [x for x in f if x.context == "E._retire"][0]
        assert "reached from E.segment" in retire.message
        assert all(x.context != "E.cold" for x in f)

    def test_quiet_without_annotation(self):
        src = self.HOT.replace("  # lint: hot-path", "")
        assert only(lint_source(src), "PT007") == []

    @pytest.mark.parametrize("jit", [
        "return jax.jit(program)",
        "return monitor.monitored_jit(program, name='p')",
        "return functools.partial(jax.jit, donate_argnums=(0,))(program)",
    ])
    def test_quiet_inside_a_jitted_nested_def(self, jit):
        """The jitted def's body, and what it calls while tracing, is
        where eager code ends; the same def NOT jitted is a closure of
        the hot function and fires."""
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def _key(seed, n):\n"
            "    return jax.random.fold_in(jax.random.PRNGKey(seed), n)\n"
            "class E:\n"
            "    def segment(self, seed):  # lint: hot-path\n"
            "        def program(seed, n):\n"
            "            return jnp.argmax(jax.random.bits(_key(seed, n)))\n"
            "        " + jit + "\n")
        assert only(lint_source(src), "PT007") == []
        eager = src.replace(jit, "return program(seed, 1)")
        assert sorted(x.detail for x in only(lint_source(eager),
                                             "PT007")) == [
            "jax.random.PRNGKey", "jax.random.bits",
            "jax.random.fold_in", "jnp.argmax"]

    def test_quiet_on_a_decorated_module_function(self):
        src = (
            "import functools\n"
            "import jax\n"
            "import jax.numpy as jnp\n"
            "@functools.partial(jax.jit, donate_argnums=(0,))\n"
            "def copy_page(pool, src, dst):\n"
            "    return pool.at[dst].set(pool[src])\n"
            "def gap(pool):  # lint: hot-path\n"
            "    return copy_page(pool, 0, 1)\n")
        assert only(lint_source(src), "PT007") == []

    def test_escape_hatch_requires_reason(self):
        src = (
            "import jax.numpy as jnp\n"
            "class E:\n"
            "    def _gap(self):  # lint: hot-path\n"
            "        # lint: allow-eager-dispatch(debug-only zeros)\n"
            "        a = jnp.zeros((4,))\n"
            "        b = jnp.ones((4,))  # lint: allow-eager-dispatch\n")
        f = only(lint_source(src), "PT007")
        assert len(f) == 1 and "REASON is required" in f[0].message


class TestEngine:
    def test_unknown_directive_is_config_error(self):
        f = lint_source("x = 1  # lint: allow-hostsync(typo)\n")
        assert [x.checker for x in f] == ["PT000"]
        assert "unknown lint directive" in f[0].message

    def test_fingerprints_stable_under_line_drift(self):
        src = ("import numpy as np\n"
               "class S:\n"
               "    def _gap(self):  # lint: hot-path\n"
               "        a = np.asarray(self.x)\n"
               "        b = np.asarray(self.y)\n")
        before = [f.fingerprint for f in lint_source(src)]
        shifted = "# a comment\n# another\n\n" + src
        after = [f.fingerprint for f in lint_source(shifted)]
        assert before == after and len(before) == 2
        # ...and the two identical details stay distinguishable
        assert before[0] != before[1]

    def test_baseline_requires_justification(self, tmp_path):
        p = tmp_path / "b.json"
        p.write_text(json.dumps({"entries": [
            {"fingerprint": "PT001|f.py|ctx|jit:x|0",
             "justification": "   "}]}))
        with pytest.raises(BaselineError):
            load_baseline(str(p))
        p.write_text("{not json")
        with pytest.raises(BaselineError):
            load_baseline(str(p))

    def test_apply_baseline_suppresses_and_reports_stale(self):
        findings = lint_source(
            "import jax\n"
            "def probe(x):\n"
            "    return jax.jit(lambda a: a)(x)\n")
        fp = findings[0].fingerprint
        baseline = {fp: {"fingerprint": fp, "justification": "ok"},
                    "PT009|gone.py|x|y|0": {
                        "fingerprint": "PT009|gone.py|x|y|0",
                        "justification": "stale"}}
        new, suppressed, stale = apply_baseline(findings, baseline)
        assert new == [] and len(suppressed) == 1
        assert stale == ["PT009|gone.py|x|y|0"]

    def test_orphaned_escape_does_not_cross_blank_line(self):
        """An escape comment whose statement was deleted (blank line
        left behind) must NOT silently suppress the next statement."""
        src = ("import numpy as np\n"
               "class S:\n"
               "    def _gap(self):  # lint: hot-path\n"
               "        # lint: allow-host-sync(stale orphan)\n"
               "\n"
               "        toks = np.asarray(self.toks_dev)\n")
        assert len(only(lint_source(src), "PT002")) == 1

    def test_unknown_directive_reported_once(self):
        src = ("# lint: allow-hostsync(typo)\n"
               "\n"
               "x = 1\n"
               "y = 2\n")
        f = [x for x in lint_source(src) if x.checker == "PT000"]
        assert len(f) == 1 and f[0].line == 1

    def test_scoped_run_neither_stales_nor_drops_foreign_entries(self):
        from tools.lint.core import generate_baseline as gen
        findings = lint_source(
            "import jax\n"
            "def probe(x):\n"
            "    return jax.jit(lambda a: a)(x)\n",
            filename="pkg/a.py")
        foreign_fp = "PT003|pkg/b.py|Pool._pages|x_pages|0"
        baseline = {foreign_fp: {"fingerprint": foreign_fp,
                                 "justification": "kept"}}
        # a run covering only pkg/a.py: the pkg/b.py entry is not stale
        new, _sup, stale = apply_baseline(
            findings, baseline, covered_files={"pkg/a.py"})
        assert stale == [] and len(new) == 1
        # ...and regeneration over that scope carries it forward
        doc = gen(findings, previous=baseline,
                  covered_files={"pkg/a.py"})
        fps = [e["fingerprint"] for e in doc["entries"]]
        assert foreign_fp in fps
        kept = [e for e in doc["entries"]
                if e["fingerprint"] == foreign_fp][0]
        assert kept["justification"] == "kept"
        # a checker-subset run is scope-bounded the same way
        _new2, _sup2, stale2 = apply_baseline(
            [], baseline, covered_files={"pkg/b.py"},
            covered_checks=["PT001"])
        assert stale2 == []
        # a FULL-scope run does declare it stale
        _new3, _sup3, stale3 = apply_baseline(
            [], baseline, covered_files={"pkg/b.py"})
        assert stale3 == [foreign_fp]

    def test_baseline_round_trip_regenerates_identically(self, tmp_path):
        findings = lint_source(
            "import jax\n"
            "def probe(x):\n"
            "    f = jax.jit(lambda a: a)\n"
            "    return f(x)\n")
        doc = generate_baseline(findings)
        doc["entries"][0]["justification"] = "a real reason"
        p = tmp_path / "baseline.json"
        write_baseline(doc, str(p))
        reloaded = load_baseline(str(p))
        doc2 = generate_baseline(findings, previous=reloaded)
        assert doc2["entries"] == doc["entries"]
        p2 = tmp_path / "baseline2.json"
        write_baseline(doc2, str(p2))
        assert p.read_text() == p2.read_text()


# ---------------------------------------------------------------------------
# the tier-1 gate + CLI
# ---------------------------------------------------------------------------
class TestRepoGate:
    def test_zero_unbaselined_findings_in_paddle_tpu(self):
        """THE bar: the live tree is clean against the checked-in
        baseline. A new recompile hazard / hot-path sync / series leak
        / unlocked guarded field / ungated seam fails HERE, at the
        violating line, before it ships."""
        findings = lint_paths([os.path.join(REPO, "paddle_tpu")],
                              root=REPO)
        baseline = load_baseline(default_baseline_path())
        new, _suppressed, stale = apply_baseline(findings, baseline)
        assert new == [], (
            "UNBASELINED lint findings (fix, annotate, or triage into "
            "tools/lint/baseline.json with a justification):\n\n"
            + "\n".join(f.render() for f in new))
        assert stale == [], (
            "stale baseline entries (the code they suppressed is gone "
            "- prune with --fix-baseline):\n" + "\n".join(stale))

    def test_checked_in_baseline_is_fully_reviewed(self):
        baseline = load_baseline(default_baseline_path())
        unreviewed = [fp for fp, e in baseline.items()
                      if e["justification"].startswith("UNREVIEWED")]
        assert unreviewed == []

    def test_hot_path_ground_truth_is_annotated(self):
        """The PT002/PT004 ground-truth annotations the linter depends
        on must stay in place — deleting one silently turns the
        checker off for that path."""
        from tools.lint.core import Module
        from tools.lint.checks.host_sync import hot_functions
        expected = {
            "paddle_tpu/serving/scheduler.py": {"Server._gap",
                                                "Server.load"},
            "paddle_tpu/serving/router.py": {"Router.load"},
            # the cross-process replica's router-facing seam: cached-
            # snapshot reads only — PT006's ground truth (PR 17)
            "paddle_tpu/serving/remote.py": {
                "RemoteReplica.status", "RemoteReplica.load",
                "RemoteReplica.num_active",
                "RemoteReplica.flight_dumps",
                "_RemoteQueue.depth", "_RemoteAlloc.free_pages",
                "_RemoteAdapters.__contains__"},
            "paddle_tpu/inference/generation.py": {
                "PagedContinuousBatchingEngine.decode_segment",
                "PagedContinuousBatchingEngine._decode_segment_plain",
                "PagedContinuousBatchingEngine._decode_segment_spec",
                "PagedContinuousBatchingEngine"
                "._decode_segment_spec_device",
                "PagedContinuousBatchingEngine.load",
                "PagedContinuousBatchingEngine.grow_for_segment",
                # the admissions sit in the same gap (PR 31)
                "PagedContinuousBatchingEngine.add_request",
                "PagedContinuousBatchingEngine.begin_admit",
                "PagedContinuousBatchingEngine.admit_chunk"},
        }
        for rel, want in expected.items():
            with open(os.path.join(REPO, rel)) as f:
                mod = Module(rel, f.read())
            got = {mod.qualname(fn) for fn in hot_functions(mod)}
            assert want <= got, f"{rel}: hot roots {want - got} missing"

    def test_cli_summary_and_exit_codes(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import jax\n"
                       "def f(x):\n"
                       "    return jax.jit(lambda a: a)(x)\n")
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        r = subprocess.run(
            [sys.executable, "-m", "tools.lint", str(bad),
             "--no-baseline"],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=120)
        assert r.returncode == 1
        assert "PT001" in r.stdout and "fingerprint:" in r.stdout
        r2 = subprocess.run(
            [sys.executable, "-m", "tools.lint", str(bad),
             "--no-baseline", "--checks", "PT003", "--summary"],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=120)
        assert r2.returncode == 0
        assert "paddle_tpu-lint summary" in r2.stdout
