"""`pio lint` (tools/analyze): the KNOWN_ISSUES invariants as passes.

Three layers, all tier-1:

1. **The repo is clean**: one entry point runs every pass over the real
   tree exactly like `pio lint` and requires exit 0 — THE static-analysis
   gate. Any new violation anywhere in `predictionio_tpu/`,
   `chip_smoke.py` or `diagnostics/` fails this test with file:line + rule + fix hint.
2. **The passes are live**: each rule is proven to fire on a seeded
   defect (a `block_until_ready` clock boundary, an unclipped padded
   gather, an implicit device->host sync, a `time.time()` inside a
   jitted body, a lock-order inversion, an undocumented `PIO_*` read,
   an unregistered serving jit, a private debug path) — a lint that
   can't fail is documentation, not enforcement.
3. **No coverage was lost in the re-homing**: the hand-maintained
   module lists of the three pre-framework lints are asserted to be
   SUBSETS of what the shared walker / structural scopes discover, so
   the old opt-in coverage is provably contained in the new opt-out
   coverage.

Plus the suppression-baseline contract (new findings fail; baselined
findings don't; stale baseline entries fail until deleted) and the
runtime lock-order monitor the chaos tests install.
"""

import ast
import json
import os
import threading

import pytest

from predictionio_tpu.tools.analyze import runner, runtime, walker
from predictionio_tpu.tools.analyze.findings import Baseline, Finding
from predictionio_tpu.tools.analyze.passes import (
    all_passes, aot_registration, debug_surface, declarations, host_sync,
    jit_purity, lock_order, timing,
)

ROOT = walker.repo_root()


def _mod(src, rel="predictionio_tpu/fake/mod.py"):
    """An in-memory Module for seeding defects into a pass."""
    return walker.Module(path=os.path.join(ROOT, rel), rel=rel,
                         source=src, tree=ast.parse(src))


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# 1. the gate: the repo itself lints clean
# ---------------------------------------------------------------------------

def test_repo_lint_clean():
    """THE tier-1 entry point: `pio lint` over the real repo, exit 0."""
    result = runner.run_lint()
    assert not result.internal_errors, result.internal_errors
    assert result.exit_code == 0, "\n" + result.render_text()
    # the walk covers the whole repo-of-record, not an opt-in list
    assert result.modules_analyzed > 100
    assert len(result.passes_run) == len(all_passes())


def test_lint_json_schema():
    """The --json object carries the documented fields (README schema)."""
    d = runner.run_lint().as_dict()
    for key in ("exit", "modulesAnalyzed", "passes", "findings",
                "suppressed", "staleBaselineKeys", "internalErrors",
                "counts"):
        assert key in d, key
    assert d["counts"] == {"findings": len(d["findings"]),
                           "suppressed": len(d["suppressed"]),
                           "stale": len(d["staleBaselineKeys"])}
    json.dumps(d)                      # JSON-serializable end to end


# ---------------------------------------------------------------------------
# 2. every pass fires on a seeded defect
# ---------------------------------------------------------------------------

def test_timing_pass_fires_on_block_until_ready_clock_boundary():
    src = (
        "import time\n"
        "import jax\n"
        "def timed(x):\n"
        "    t0 = time.perf_counter()\n"
        "    y = x + 1\n"
        "    jax.block_until_ready(y)\n"     # the KNOWN_ISSUES #3 shape
        "    return time.perf_counter() - t0\n")
    assert _rules(timing.run([_mod(src)])) == ["timing-block-until-ready"]


def test_timing_pass_fires_on_wall_clock():
    src = "import time as t\nx = t.time()\nfrom time import time\ny = time()\n"
    found = timing.run([_mod(src)])
    assert _rules(found) == ["timing-wall-clock"]
    assert sorted(f.line for f in found) == [2, 4]
    # perf_counter does not trip it
    assert not timing.run([_mod("import time\nx = time.perf_counter()\n")])


def test_timing_pass_respects_pragma_opt_out():
    src = ("import jax\n"
           "# dispatch barrier, nothing timed behind it\n"
           "jax.block_until_ready(0)  "
           "# pio-lint: allow=timing-block-until-ready\n")
    assert not timing.run([_mod(src)])


def test_host_sync_pass_fires_on_unclipped_gather():
    src = ("import jax.numpy as jnp\n"
           "def f(x, idx):\n"
           "    return jnp.take(x, idx, axis=0)\n")
    assert _rules(host_sync.run([_mod(src)])) == ["gather-clip"]


def test_host_sync_pass_accepts_clipped_and_contracted_gathers():
    clipped = ("import jax.numpy as jnp\n"
               "def f(x, idx, n):\n"
               "    idx = jnp.clip(idx, 0, n - 1)\n"
               "    return jnp.take(x, idx, axis=0)\n")
    mode = ("import jax.numpy as jnp\n"
            "def f(x, idx):\n"
            "    return jnp.take(x, idx, axis=0, mode='clip')\n")
    contract = ("import jax.numpy as jnp\n"
                "def f(x, idx):\n"
                '    """idx must be in-bounds (callers clip)."""\n'
                "    return jnp.take(x, idx, axis=0)\n")
    for src in (clipped, mode, contract):
        assert not host_sync.run([_mod(src)]), src


def test_host_sync_pass_fires_on_implicit_sync():
    src = ("import jax.numpy as jnp\n"
           "def serve(q):\n"
           "    scores = jnp.dot(q, q)\n"
           "    return float(scores)\n")       # implicit device->host sync
    assert _rules(host_sync.run([_mod(src)])) == ["hostsync-implicit"]
    # the sanctioned explicit transfer is NOT flagged
    ok = ("import jax\nimport jax.numpy as jnp\n"
          "def serve(q):\n"
          "    return float(jax.device_get(jnp.dot(q, q)))\n")
    assert not host_sync.run([_mod(ok)])


def test_host_sync_pass_fires_inside_registered_jit_bodies():
    """A conversion inside a register_jit-reachable body is flagged even
    with no local jax provenance — the argument IS a tracer there."""
    src = ("import jax.numpy as jnp\n"
           "from predictionio_tpu.serving.aot import register_jit\n"
           "def kernel(x, k):\n"
           "    return jnp.sum(x) * int(k)\n"
           "register_jit('kernel', kernel)\n")
    assert _rules(host_sync.run([_mod(src)])) == ["hostsync-implicit"]


def test_jit_purity_pass_fires_on_wall_clock_in_jit():
    src = ("import time\nimport jax\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    return x + time.time()\n")     # baked in at trace time
    assert _rules(jit_purity.run([_mod(src)])) == ["jit-wall-clock"]


def test_jit_purity_pass_fires_on_rng_io_and_global_mutation():
    src = ("import random\nimport jax\n"
           "STATE = {}\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    global STATE\n"
           "    print(x)\n"
           "    return x * random.random()\n")
    assert _rules(jit_purity.run([_mod(src)])) == [
        "jit-global-mutation", "jit-io", "jit-nondeterminism"]
    # jax.random with an explicit key is the traced alternative: legal
    ok = ("import jax\n"
          "@jax.jit\n"
          "def f(key, x):\n"
          "    return x + jax.random.normal(key, x.shape)\n")
    assert not jit_purity.run([_mod(ok)])


def test_jit_purity_ignores_unjitted_functions():
    src = ("import time\nimport jax\n"
           "def eager(x):\n"
           "    return x + time.time()\n")     # wrong-clock maybe, but
    assert not jit_purity.run([_mod(src)])     # not a jit-purity issue


def test_lock_order_pass_fires_on_inversion():
    src = (
        "import threading\n"
        "a_lock = threading.Lock()\n"
        "b_lock = threading.Lock()\n"
        "def path_one():\n"
        "    with a_lock:\n"
        "        with b_lock:\n"
        "            pass\n"
        "def path_two():\n"
        "    with b_lock:\n"
        "        with a_lock:\n"
        "            pass\n")
    found = lock_order.run([_mod(src)])
    assert _rules(found) == ["lock-order-inversion"]
    assert "a_lock" in found[0].message and "b_lock" in found[0].message


def test_lock_order_pass_accepts_consistent_order():
    src = (
        "import threading\n"
        "a_lock = threading.Lock()\n"
        "b_lock = threading.Lock()\n"
        "def path_one():\n"
        "    with a_lock:\n"
        "        with b_lock:\n"
        "            pass\n"
        "def path_two():\n"
        "    with a_lock:\n"
        "        with b_lock:\n"
        "            pass\n")
    assert not lock_order.run([_mod(src)])


def test_lock_order_distinguishes_classes():
    """self._lock of two different classes are different nodes."""
    src = (
        "class A:\n"
        "    def f(self):\n"
        "        with self._lock:\n"
        "            with self._cond:\n"
        "                pass\n"
        "class B:\n"
        "    def g(self):\n"
        "        with self._cond:\n"
        "            with self._lock:\n"
        "                pass\n")
    # A._lock->A._cond and B._cond->B._lock: four distinct nodes, no pair
    assert not lock_order.run([_mod(src)])
    graph = lock_order.build_graph([_mod(src)])
    assert len(graph) == 2


def test_declarations_pass_fires_on_undocumented_env_read():
    src = "import os\nx = os.environ.get('PIO_NOT_A_REAL_KNOB_XYZ', '')\n"
    found = [f for f in declarations.run([_mod(src)], readme_text="")
             if f.path != declarations._DECL_REL]
    assert _rules(found) == ["env-undeclared"]
    assert "PIO_NOT_A_REAL_KNOB_XYZ" in found[0].message


def test_declarations_pass_fires_on_unregistered_metric():
    src = ("from predictionio_tpu.common import telemetry\n"
           "c = telemetry.registry.counter('pio_ghost_series_total', 'x')\n")
    found = [f for f in declarations.run([_mod(src)], readme_text="")
             if f.rule == "metric-undeclared"]
    assert len(found) == 1 and "pio_ghost_series_total" in found[0].message


def test_declarations_pass_fires_on_undeclared_journal_category():
    """The journal-category half of the declarations triangle: an emit
    call site whose category is not in JOURNAL_CATEGORIES is a typo'd
    timeline and fails the lint."""
    src = ("from predictionio_tpu.common import journal\n"
           "journal.emit('not_a_real_category_xyz', 'boom')\n")
    found = [f for f in declarations.run([_mod(src)], readme_text="")
             if f.rule == "journal-undeclared"]
    assert len(found) == 1
    assert "not_a_real_category_xyz" in found[0].message
    # keyword spelling is caught too
    src_kw = ("from predictionio_tpu.common import journal\n"
              "journal.emit(category='also_bogus_xyz', message='x')\n")
    found = [f for f in declarations.run([_mod(src_kw)], readme_text="")
             if f.rule == "journal-undeclared"]
    assert len(found) == 1 and "also_bogus_xyz" in found[0].message


def test_declarations_pass_accepts_declared_journal_category():
    src = ("from predictionio_tpu.common import journal\n"
           "journal.emit('wal', 'repaired', level=journal.WARN)\n")
    assert not [f for f in declarations.run([_mod(src)], readme_text="")
                if f.rule == "journal-undeclared"]


def test_declarations_pass_fires_on_undeclared_tenant_metric():
    """The multi-tenant subsystem is inside the declarations triangle:
    a tenant-labeled family NOT in METRICS fails the pass, while the
    registered pio_tenant_* families, the PIO_TENANT_* env knobs, and
    the 'tenant' journal category all pass."""
    src = ("from predictionio_tpu.common import telemetry\n"
           "c = telemetry.registry().counter(\n"
           "    'pio_tenant_evictions_total', 'x',\n"
           "    labelnames=('tenant',))\n")
    found = [f for f in declarations.run(
        [_mod(src, rel="predictionio_tpu/serving/registry.py")],
        readme_text="") if f.rule == "metric-undeclared"]
    assert len(found) == 1
    assert "pio_tenant_evictions_total" in found[0].message

    ok = ("import os\n"
          "from predictionio_tpu.common import journal, telemetry\n"
          "r = os.environ.get('PIO_TENANT_RATE', '')\n"
          "h = os.environ.get('PIO_TENANT_HBM_HARD_CAP_MB', '')\n"
          "c = telemetry.registry().counter(\n"
          "    'pio_tenant_requests_total', 'x',\n"
          "    labelnames=('tenant', 'outcome'))\n"
          "journal.emit('tenant', 'over budget', level=journal.WARN)\n")
    found = declarations.run(
        [_mod(ok, rel="predictionio_tpu/serving/registry.py")],
        readme_text="")
    assert not [f for f in found if f.rule in (
        "metric-undeclared", "env-undeclared", "journal-undeclared")]


def test_declarations_pass_covers_history_knobs_and_metrics():
    """The metrics flight recorder is inside the declarations triangle:
    an undeclared PIO_HISTORY_* knob and a ghost pio_history_* family
    each fire exactly one finding, while the real knobs and the
    sampler's registered families pass clean."""
    bad_env = ("import os\n"
               "x = os.environ.get('PIO_HISTORY_BOGUS_KNOB', '')\n")
    found = [f for f in declarations.run([_mod(bad_env)], readme_text="")
             if f.path != declarations._DECL_REL]
    assert _rules(found) == ["env-undeclared"]
    assert "PIO_HISTORY_BOGUS_KNOB" in found[0].message

    bad_metric = (
        "from predictionio_tpu.common import telemetry\n"
        "c = telemetry.registry().counter(\n"
        "    'pio_history_bogus_total', 'x')\n")
    found = [f for f in declarations.run(
        [_mod(bad_metric, rel="predictionio_tpu/common/history.py")],
        readme_text="") if f.rule == "metric-undeclared"]
    assert len(found) == 1
    assert "pio_history_bogus_total" in found[0].message

    ok = ("import os\n"
          "from predictionio_tpu.common import telemetry\n"
          "t = os.environ.get('PIO_HISTORY_TICK_S', '5')\n"
          "m = os.environ.get('PIO_HISTORY_MAX_SERIES', '512')\n"
          "e = os.environ.get('PIO_HISTORY', '1')\n"
          "c = telemetry.registry().counter(\n"
          "    'pio_history_ticks_total', 'x')\n"
          "g = telemetry.registry().gauge('pio_history_series', 'x')\n")
    found = declarations.run(
        [_mod(ok, rel="predictionio_tpu/common/history.py")],
        readme_text="")
    assert not [f for f in found if f.rule in (
        "metric-undeclared", "env-undeclared")]


def test_declarations_pass_covers_partition_and_cache_families():
    """The partition-routing + response-cache subsystem is inside the
    declarations triangle: a ghost cache metric and an undeclared
    PIO_ROUTER_CACHE_* knob both fail the pass, while the real env
    knobs and metric families registered by router/create_server
    pass clean."""
    bad_metric = (
        "from predictionio_tpu.common import telemetry\n"
        "c = telemetry.registry().counter(\n"
        "    'pio_router_cache_ghost_total', 'x')\n")
    found = [f for f in declarations.run(
        [_mod(bad_metric, rel="predictionio_tpu/workflow/router.py")],
        readme_text="") if f.rule == "metric-undeclared"]
    assert len(found) == 1
    assert "pio_router_cache_ghost_total" in found[0].message

    bad_env = ("import os\n"
               "x = os.environ.get('PIO_ROUTER_CACHE_GHOST_KNOB', '')\n")
    found = [f for f in declarations.run(
        [_mod(bad_env, rel="predictionio_tpu/workflow/router.py")],
        readme_text="") if f.path != declarations._DECL_REL]
    assert _rules(found) == ["env-undeclared"]

    ok = ("import os\n"
          "from predictionio_tpu.common import journal, telemetry\n"
          "a = os.environ.get('PIO_ROUTER_CACHE', 'off')\n"
          "b = os.environ.get('PIO_ROUTER_CACHE_MB', '16')\n"
          "c = os.environ.get('PIO_ROUTER_CACHE_TTL_MS', '5000')\n"
          "d = os.environ.get('PIO_DEPLOY_PARTITION', '')\n"
          "reg = telemetry.registry()\n"
          "reg.counter('pio_router_cache_hits_total', 'x')\n"
          "reg.counter('pio_router_cache_misses_total', 'x')\n"
          "reg.counter('pio_router_cache_evictions_total', 'x')\n"
          "reg.gauge('pio_router_cache_hit_ratio', 'x')\n"
          "reg.counter('pio_router_partition_requests_total', 'x',\n"
          "            labelnames=('outcome',))\n"
          "reg.gauge('pio_router_partition_width', 'x')\n"
          "journal.emit('router', 'partition map live',\n"
          "             level=journal.INFO)\n")
    found = declarations.run(
        [_mod(ok, rel="predictionio_tpu/workflow/router.py")],
        readme_text="")
    assert not [f for f in found if f.rule in (
        "metric-undeclared", "env-undeclared", "journal-undeclared")]


def test_declarations_pass_fires_on_undeclared_category_in_realtime():
    """The new realtime subsystem is inside the journal-undeclared
    scope like everything else: a fold-in emitter with a typo'd
    category fails the lint, and its real `foldin` category passes."""
    src = ("from predictionio_tpu.common import journal\n"
           "journal.emit('fold_in_typo_xyz', 'headroom gone',\n"
           "             level=journal.WARN)\n")
    found = [f for f in declarations.run(
        [_mod(src, rel="predictionio_tpu/realtime/foldin.py")],
        readme_text="") if f.rule == "journal-undeclared"]
    assert len(found) == 1 and "fold_in_typo_xyz" in found[0].message
    ok = ("from predictionio_tpu.common import journal\n"
          "journal.emit('foldin', 'worker bound',\n"
          "             level=journal.INFO)\n")
    assert not [f for f in declarations.run(
        [_mod(ok, rel="predictionio_tpu/realtime/foldin.py")],
        readme_text="") if f.rule == "journal-undeclared"]


def test_declarations_pass_covers_autopilot_families():
    """ISSUE 18 seeded defect: the autopilot subsystem sits inside the
    declarations triangle like every other — an undeclared
    pio_autopilot_* metric fires exactly one finding, while the real
    autopilot metrics, PIO_AUTOPILOT_* knobs, and the `autopilot`
    journal category all pass."""
    bad = ("from predictionio_tpu.common import telemetry\n"
           "c = telemetry.registry().counter(\n"
           "    'pio_autopilot_bogus_total', 'x',\n"
           "    labelnames=('action',))\n")
    found = [f for f in declarations.run(
        [_mod(bad, rel="predictionio_tpu/workflow/autopilot.py")],
        readme_text="") if f.rule == "metric-undeclared"]
    assert len(found) == 1
    assert "pio_autopilot_bogus_total" in found[0].message

    ok = ("import os\n"
          "from predictionio_tpu.common import journal, telemetry\n"
          "a = os.environ.get('PIO_AUTOPILOT_COOLDOWN_S', '30')\n"
          "b = os.environ.get('PIO_AUTOPILOT_UTIL_HIGH', '0.85')\n"
          "reg = telemetry.registry()\n"
          "reg.counter('pio_autopilot_actions_total', 'x',\n"
          "            labelnames=('action', 'outcome'))\n"
          "reg.gauge('pio_autopilot_state', 'x')\n"
          "reg.gauge('pio_autopilot_last_action_age_seconds', 'x')\n"
          "journal.emit('autopilot', 'shed widened',\n"
          "             level=journal.WARN)\n")
    found = declarations.run(
        [_mod(ok, rel="predictionio_tpu/workflow/autopilot.py")],
        readme_text="")
    assert not [f for f in found if f.rule in (
        "metric-undeclared", "env-undeclared", "journal-undeclared")]


def test_declarations_pass_clean_on_real_repo_and_readme():
    """Every PIO_* read, pio_* metric, and journal.emit category in the
    real tree is declared in common/declarations.py and (env/metric)
    documented in README.md."""
    modules = [m for m in walker.discover(ROOT)]
    assert not declarations.run(modules)


def test_aot_pass_fires_on_unregistered_serving_jit():
    src = ("import jax\n"
           "@jax.jit\n"
           "def brand_new_kernel(x):\n"
           "    return x\n")
    found = aot_registration.run(
        [_mod(src, rel="predictionio_tpu/serving/newmod.py")])
    assert _rules(found) == ["aot-unregistered-jit"]
    assert found[0].detail == "brand_new_kernel"


def test_aot_pass_scope_is_structural_not_a_list():
    """A module OUTSIDE serving/ that registers kernels is pulled into
    scope automatically — the PR 8 hand-extension becomes unnecessary."""
    src = ("import jax\n"
           "from predictionio_tpu.serving.aot import register_jit\n"
           "@jax.jit\n"
           "def registered(x):\n"
           "    return x\n"
           "@jax.jit\n"
           "def forgotten(x):\n"
           "    return x\n"
           "register_jit('registered', registered)\n")
    found = aot_registration.run(
        [_mod(src, rel="predictionio_tpu/parallel/newdist.py")])
    assert [f.detail for f in found] == ["forgotten"]


def test_aot_pass_fires_on_unregistered_quant_kernel():
    """ISSUE 11 seeded defect: a quantized serving module that registers
    one kernel but forgets its fused sibling — the forgotten one would
    compile lazily on the first quantized request, exactly the cliff
    the AOT pass exists to catch."""
    src = ("import jax\n"
           "from predictionio_tpu.serving.aot import register_jit\n"
           "@jax.jit\n"
           "def topk_quant(x):\n"
           "    return x\n"
           "@jax.jit\n"
           "def topk_quant_fused(x):\n"
           "    return x\n"
           "register_jit('topk_quant', topk_quant)\n")
    found = aot_registration.run(
        [_mod(src, rel="predictionio_tpu/ops/quant_v2.py")])
    assert _rules(found) == ["aot-unregistered-jit"]
    assert [f.detail for f in found] == ["topk_quant_fused"]


def test_aot_scope_covers_quant_modules_automatically():
    """ops/quant.py enters the AOT lint scope via register_jit
    reachability — no hand-maintained list was touched."""
    modules = walker.discover(ROOT)
    scope = {m.rel for m in aot_registration.serving_scope(modules)}
    assert "predictionio_tpu/ops/quant.py" in scope


def test_debug_surface_pass_fires_on_private_path():
    telemetry_src = "DEBUG_PATHS = ('/debug/slow.json',)\n"
    offender = "PATH = '/debug/private.json'\n"
    mods = [_mod(telemetry_src, rel="predictionio_tpu/common/telemetry.py"),
            _mod(offender, rel="predictionio_tpu/data/api/service.py")]
    found = debug_surface.run(mods)
    assert "debug-path-unshared" in _rules(found)
    # shared paths and their query-bearing forms stay legal
    ok = "PATH = '/debug/slow.json?limit=3'\n"
    mods[1] = _mod(ok, rel="predictionio_tpu/data/api/service.py")
    assert "debug-path-unshared" not in _rules(debug_surface.run(mods))


# ---------------------------------------------------------------------------
# 3. re-homing lost no coverage: old opt-in lists ⊂ new opt-out scopes
# ---------------------------------------------------------------------------

#: the hand-maintained scope lists of the three pre-framework lints,
#: frozen as they stood before the re-homing (tests/test_timing_lint.py
#: and tests/test_aot.py at PR 8). They exist here ONLY to prove
#: containment — the passes themselves carry no lists.
_OLD_TIMED_MODULES = (
    "common/telemetry.py", "common/tracing.py", "common/devicewatch.py",
    "common/waterfall.py", "common/profiling.py", "common/slo.py",
    "serving/batcher.py", "serving/aot.py", "parallel/serve_dist.py",
    "workflow/context.py", "workflow/core_workflow.py",
    "workflow/create_server.py", "data/store.py", "ops/staging.py",
    "models/recommendation/als_algorithm.py",
    "tools/doctor.py", "tools/profile.py",
)
_OLD_AOT_MODULES = ("ops/topk.py", "parallel/serve_dist.py")  # + serving/*
_OLD_DAEMON_MODULES = (
    "workflow/create_server.py", "data/api/service.py",
    "data/storage/remote.py",
    # PR 15: the fleet router is a fourth daemon with the same shared
    # debug surface contract
    "workflow/router.py",
    # PR 20: the eval dashboard + admin server joined the contract so
    # `pio monitor` can scrape all six daemons without a key
    "tools/dashboard.py", "tools/admin.py",
)


def test_timing_coverage_superset_of_old_list():
    discovered = {m.rel for m in walker.discover(ROOT)}
    old = {f"predictionio_tpu/{rel}" for rel in _OLD_TIMED_MODULES}
    assert old <= discovered, sorted(old - discovered)
    # and strictly more: chip_smoke.py + diagnostics/ joined the walk
    assert "chip_smoke.py" in discovered
    assert any(r.startswith("diagnostics/") for r in discovered)


def test_aot_scope_superset_of_old_list():
    modules = walker.discover(ROOT)
    scope = {m.rel for m in aot_registration.serving_scope(modules)}
    old = {f"predictionio_tpu/{rel}" for rel in _OLD_AOT_MODULES}
    old |= {m.rel for m in modules
            if m.rel.startswith("predictionio_tpu/serving/")}
    assert old <= scope, sorted(old - scope)
    # the training-kernel module register_jit resolves into is in scope
    # too — the old lint never covered it
    assert "predictionio_tpu/ops/als.py" in scope


def test_debug_daemon_set_matches_old_list():
    assert {f"predictionio_tpu/{rel}" for rel in _OLD_DAEMON_MODULES} == set(
        debug_surface.DAEMON_MODULES)


def test_registered_jit_defs_resolve_cross_module():
    """The purity/host-sync jit scope follows register_jit into other
    modules (ops/als.py's training kernels are traced bodies too)."""
    modules = walker.discover(ROOT)
    regs = {(m.rel, fn.name) for m, fn in walker.registered_jit_defs(modules)}
    assert ("predictionio_tpu/ops/als.py", "_train_hybrid_jit") in regs
    assert any(rel == "predictionio_tpu/ops/topk.py" for rel, _ in regs)


# ---------------------------------------------------------------------------
# suppression baseline: the debt contract
# ---------------------------------------------------------------------------

def test_baseline_suppresses_known_and_fails_new(tmp_path):
    known = Finding(rule="r", path="a.py", line=3, message="m", detail="tok")
    new = Finding(rule="r", path="b.py", line=9, message="m", detail="tok2")
    path = tmp_path / "base.json"
    Baseline(path=str(path)).write(findings=[known])
    base = Baseline.load(str(path))
    active, suppressed, stale = base.apply([known, new])
    assert [f.key for f in active] == [new.key]
    assert [f.key for f in suppressed] == [known.key]
    assert stale == []


def test_baseline_key_survives_line_drift(tmp_path):
    """Keys are detail-token based, not line based: an edit above the
    accepted site must not resurrect the finding."""
    before = Finding(rule="r", path="a.py", line=3, message="m", detail="t")
    after = Finding(rule="r", path="a.py", line=47, message="m", detail="t")
    path = tmp_path / "base.json"
    Baseline(path=str(path)).write(findings=[before])
    active, suppressed, _ = Baseline.load(str(path)).apply([after])
    assert not active and [f.key for f in suppressed] == [after.key]


def test_stale_baseline_entry_fails_the_lint(tmp_path):
    gone = Finding(rule="r", path="a.py", line=3, message="m", detail="t")
    path = tmp_path / "base.json"
    Baseline(path=str(path)).write(findings=[gone])
    active, suppressed, stale = Baseline.load(str(path)).apply([])
    assert stale == [gone.key]
    # the runner turns stale keys into failing findings
    from predictionio_tpu.tools.analyze.findings import stale_findings
    rendered = stale_findings(stale, str(path))
    assert rendered and rendered[0].rule == "baseline-stale"


def test_checked_in_baseline_entries_all_match():
    """Every entry in conf/lint_baseline.json still matches a live
    finding (no stale debt) and carries a real reason."""
    result = runner.run_lint()
    assert result.stale == []
    with open(os.path.join(ROOT, "conf", "lint_baseline.json"),
              encoding="utf-8") as f:
        payload = json.load(f)
    for entry in payload["entries"]:
        assert entry["reason"], entry["key"]
        assert entry["reason"] != "accepted pre-existing finding", (
            f"placeholder reason on {entry['key']} — say WHY the debt "
            "is accepted")


def test_runner_reports_parse_failures_as_internal_error(tmp_path):
    """A file that doesn't parse is coverage loss = exit 2, not exit 0."""
    root = tmp_path
    pkg = root / "predictionio_tpu"
    pkg.mkdir()
    (pkg / "broken.py").write_text("def f(:\n")
    result = runner.run_lint(root=str(root),
                             baseline_path=str(root / "base.json"))
    assert result.exit_code == 2
    assert any("broken.py" in e for e in result.internal_errors)


def test_pragma_lives_on_line_or_line_above():
    src_same = "import time as t\nx = t.time()  # pio-lint: allow=timing-wall-clock\n"
    src_above = ("import time as t\n"
                 "# pio-lint: allow=timing-wall-clock\n"
                 "x = t.time()\n")
    assert not timing.run([_mod(src_same)])
    assert not timing.run([_mod(src_above)])
    # and a pragma for a DIFFERENT rule does not suppress
    src_wrong = "import time as t\nx = t.time()  # pio-lint: allow=gather-clip\n"
    assert timing.run([_mod(src_wrong)])


# ---------------------------------------------------------------------------
# runtime lock-order monitor (the chaos tests' dynamic half)
# ---------------------------------------------------------------------------

def test_runtime_monitor_detects_inversion():
    mon = runtime.LockOrderMonitor()
    a = mon.wrap(threading.Lock(), "a")
    b = mon.wrap(threading.Lock(), "b")
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    assert mon.inversions() == [("a", "b")]
    mon.reset()
    assert mon.inversions() == []


def test_runtime_monitor_consistent_order_is_clean_across_threads():
    mon = runtime.LockOrderMonitor()
    a = mon.wrap(threading.Lock(), "a")
    b = mon.wrap(threading.Lock(), "b")

    def worker():
        for _ in range(50):
            with a:
                with b:
                    pass

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert mon.inversions() == []
    assert mon.edges()[("a", "b")] == 200


def test_runtime_monitor_reentrant_acquire_is_not_an_edge():
    mon = runtime.LockOrderMonitor()
    r = mon.wrap(threading.RLock(), "r")
    with r:
        with r:
            pass
    assert mon.edges() == {}


def test_runtime_monitor_wraps_condition():
    """A wrapped Condition keeps wait/notify working (proxied through)."""
    mon = runtime.LockOrderMonitor()
    cond = mon.wrap(threading.Condition(), "cond")
    hits = []

    def waiter():
        with cond:
            while not hits:
                cond.wait(timeout=5)

    t = threading.Thread(target=waiter)
    t.start()
    with cond:
        hits.append(1)
        cond.notify()
    t.join(timeout=5)
    assert not t.is_alive()


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------

def test_cli_lint_exit_codes(capsys):
    assert runner.main([]) == 0
    out = capsys.readouterr().out
    assert "0 finding(s)" in out


def test_cli_lint_list_names_every_pass(capsys):
    assert runner.main(["--list"]) == 0
    out = capsys.readouterr().out
    for p in all_passes():
        assert p.name in out


def test_cli_lint_finds_seeded_defect_in_tree(tmp_path, capsys):
    pkg = tmp_path / "predictionio_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text("import time\nx = time.time()\n")
    rc = runner.main(["--root", str(tmp_path),
                      "--baseline", str(tmp_path / "base.json")])
    out = capsys.readouterr().out
    assert rc == 1
    assert "timing-wall-clock" in out and "bad.py:2" in out
    # --update-baseline accepts it; the re-run is clean; fixing the file
    # makes the baseline entry stale and the lint fails again
    assert runner.main(["--root", str(tmp_path),
                        "--baseline", str(tmp_path / "base.json"),
                        "--update-baseline"]) == 0
    capsys.readouterr()
    assert runner.main(["--root", str(tmp_path),
                        "--baseline", str(tmp_path / "base.json")]) == 0
    capsys.readouterr()
    (pkg / "bad.py").write_text("import time\nx = time.perf_counter()\n")
    rc = runner.main(["--root", str(tmp_path),
                      "--baseline", str(tmp_path / "base.json")])
    out = capsys.readouterr().out
    assert rc == 1 and "baseline-stale" in out


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
