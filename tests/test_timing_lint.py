"""Timing honesty + debug-surface unity: the runtime halves, plus the
tier-1 delegation onto the `pio lint` passes.

The static AST lints that lived here pre-PR 9 (no `time.time()`, no
`block_until_ready` in timed modules, every `/debug/*` path on the
shared route) are now passes on the shared walker
(tools/analyze/passes/timing.py, debug_surface.py) — repo-wide with
opt-OUT pragmas instead of this file's old hand-maintained opt-in
lists. The tests below run those passes over the real tree so the rules
still gate tier-1 from their historical home; seeded-defect proofs and
the old-list-containment assertions live in tests/test_lint.py.

What stays here natively is what static analysis cannot see: the
runtime half of the debug-surface rule (every DEBUG_PATHS surface
actually answers 200 on live daemon APIs).
"""

import pytest

from predictionio_tpu.tools.analyze.passes import debug_surface, timing
from predictionio_tpu.tools.analyze.walker import discover


def _active(findings):
    """Pragma handling happens inside the passes; anything returned is
    an active violation."""
    return [f"{f.path}:{f.line} [{f.rule}]" for f in findings]


def test_no_wall_clock_time_in_package():
    """No time.time() anywhere in the repo-of-record: durations come
    from time.perf_counter() (monotonic — a wall-clock delta can go
    NEGATIVE mid-measurement under NTP steps), wall-clock timestamps
    from timezone-aware datetime. Now covers chip_smoke.py and diagnostics/
    too, not just the package."""
    findings = [f for f in timing.run(discover())
                if f.rule == "timing-wall-clock"]
    assert not findings, "\n  ".join(_active(findings))


def test_no_block_until_ready_anywhere():
    """block_until_ready can return before results land on host
    (KNOWN_ISSUES #3): timed regions end in a real host transfer
    (jax.device_get). Was opt-IN over 18 listed modules; now every
    module is covered and legitimate non-timing barriers opt OUT in
    their own source with a justified pragma."""
    findings = [f for f in timing.run(discover())
                if f.rule == "timing-block-until-ready"]
    assert not findings, "\n  ".join(_active(findings))


def test_debug_surface_unified():
    """Every /debug/* path rides telemetry.DEBUG_PATHS and all three
    daemons consult telemetry.handle_route (KNOWN shape: the event
    server once lacked a surface the query server had)."""
    findings = debug_surface.run(discover())
    assert not findings, "\n  ".join(_active(findings))


def test_debug_paths_parse_from_telemetry_source():
    """The pass reads DEBUG_PATHS statically (no jax import); it must
    agree with the imported module — if the assignment ever becomes
    dynamic the pass would abstain and this test catches it."""
    from predictionio_tpu.common import telemetry
    parsed = debug_surface.shared_debug_paths(discover())
    assert parsed == set(telemetry.DEBUG_PATHS)


def test_debug_paths_answer_on_event_and_storage_daemons(memory_storage):
    """Runtime half of the lint: every DEBUG_PATHS surface answers
    (non-404) on the cheap daemons — the event server, the storage
    server, the fleet router (a backendless one constructs fine; its
    debug surface is independent of the fleet's health), and the keyed
    dashboard + admin servers (their telemetry surface answers BEFORE
    auth — a scraper or `pio monitor` holds no key). The query server's
    identical surface is covered by the waterfall e2e test (it needs a
    trained model)."""
    import socket

    from predictionio_tpu.common import telemetry
    from predictionio_tpu.data.api import EventAPI
    from predictionio_tpu.data.storage.remote import StorageRPCAPI
    from predictionio_tpu.tools.admin import AdminAPI
    from predictionio_tpu.tools.dashboard import DashboardAPI
    from predictionio_tpu.workflow.router import RouterAPI, RouterConfig
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    dead_port = s.getsockname()[1]
    s.close()
    router = RouterAPI(RouterConfig(
        backends=(f"http://127.0.0.1:{dead_port}",), health_ms=50.0))
    apis = (EventAPI(storage=memory_storage),
            StorageRPCAPI(memory_storage, key="sekrit"),
            DashboardAPI(storage=memory_storage, server_key="sekrit"),
            AdminAPI(storage=memory_storage, server_key="sekrit"),
            router)
    try:
        for api in apis:
            for path in telemetry.DEBUG_PATHS:
                response = api.handle("GET", path)
                assert response[0] == 200, (type(api).__name__, path,
                                            response)
    finally:
        router.close()


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
