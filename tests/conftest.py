import os

# The suite runs on the CPU, whatever the machine holds: 8 virtual
# devices so mesh/collective tests need no TPU hardware. The chip is
# reached only through chip_smoke.py.
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from tier-1 runs")
    config.addinivalue_line(
        "markers",
        "faults: fault-injection / resilience tests (fast, tier-1 "
        "eligible; see paddle_tpu/fluid/resilience.py)")
    config.addinivalue_line(
        "markers",
        "multihost: spawns real worker subprocesses (jax.distributed / "
        "FileStore fleets); needs free ports + process spawn headroom")
    config.addinivalue_line(
        "markers",
        "perf: performance-path tests (compile-cache warm starts, "
        "pipelined dispatch)")
    config.addinivalue_line(
        "markers",
        "analysis: static-analyzer tests (paddle_tpu.analysis: "
        "verifier/shape checker/TPU-lint/scope sanitizer)")
    config.addinivalue_line(
        "markers",
        "chaos: serving-fleet kill/brownout drills (replica SIGKILL, "
        "fault-site drills)")
    config.addinivalue_line(
        "markers",
        "planner: auto-parallelism planner tests (paddle_tpu.planner "
        "search/pricing/CLI)")
    config.addinivalue_line(
        "markers",
        "disagg: disaggregated prefill/decode serving tests "
        "(paddle_tpu.serving.disagg: KV handoff wire, prefill fleet, "
        "session-affine router, tenancy)")
    config.addinivalue_line(
        "markers",
        "integrity: data-integrity tests (paddle_tpu.integrity: "
        "digest envelopes, corrupt= fault arms, SDC sentinel + "
        "quarantine)")
    config.addinivalue_line(
        "markers",
        "spec: speculative-decoding + KV-reuse tests "
        "(paddle_tpu.serving: DraftModel block-verify bit-exactness, "
        "PrefixPool adopt/delta-prefill parity, SessionTier "
        "hibernate/resume)")
    config.addinivalue_line(
        "markers",
        "retrieval: embedding & retrieval serving tests "
        "(paddle_tpu.retrieval: ep-sharded table lookup bit-exactness, "
        "distributed-linalg parity, RetrievalEngine through registry/"
        "HTTP, ladder lint + HBM budget)")


@pytest.fixture()
def served_equal():
    """``served_equal(out, ref)``: a response the serving stack returned
    equals ``Predictor.run`` of that request alone to within 16 units in
    the last place of float32. A coalesced, padded micro-batch and a
    single-request run are different XLA programs (different batch
    shapes), and the CPU backend may round a float32 product differently
    in each; through a softmax that is 1-7 units here, most on the small
    outputs (``exp(x - max)`` carries the rounding of ``x - max``). Two
    model versions differ by millions of units. Two runs of the SAME
    shape are compared bit for bit, not with this."""
    def equal(out, ref):
        out, ref = np.asarray(out), np.asarray(ref)
        return (out.shape == ref.shape and out.dtype == ref.dtype
                and bool(np.all(np.abs(out - ref)
                                <= 16 * np.spacing(np.abs(ref)))))
    return equal


@pytest.fixture()
def armed_sanitizers():
    """Arm the lock-order/thread sanitizer and the scope sanitizer for
    one test, then assert it recorded ZERO violations. Chaos drills use
    this: kill/brownout paths must stay deadlock-free, convoy-free, and
    leak-free even while replicas die mid-stream."""
    from paddle_tpu.analysis import concurrency, sanitizer

    was_conc, was_scope = concurrency.armed(), sanitizer.armed()
    # the thread registry outlives a test: a thread an EARLIER test of
    # this worker left running (slow to exit on a shared CPU) is that
    # test's, and is not charged to the drill that happens to run next
    before = set(concurrency.live_threads())
    concurrency.arm()
    concurrency.reset()
    sanitizer.arm()
    sanitizer.reset()
    try:
        yield
        conc_v = concurrency.violations()
        scope_v = sanitizer.violations()
        leaked = [t.name for t in concurrency.live_threads()
                  if t not in before]
    finally:
        if not was_conc:
            concurrency.disarm()
        if not was_scope:
            sanitizer.disarm()
        concurrency.reset()
        sanitizer.reset()
    assert conc_v == [], conc_v
    assert scope_v == [], scope_v
    assert leaked == [], leaked


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Give every test fresh default programs + scope + name generator."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.fluid import executor as executor_mod

    old_main = framework.switch_main_program(framework.Program())
    old_startup = framework.switch_startup_program(framework.Program())
    old_gen = unique_name.switch()
    old_scope = executor_mod._scope_stack[:]
    executor_mod._scope_stack[:] = [executor_mod.Scope()]
    yield
    framework.switch_main_program(old_main)
    framework.switch_startup_program(old_startup)
    unique_name.switch(old_gen)
    executor_mod._scope_stack[:] = old_scope
