"""bench.py always-emit guard (ISSUE 5 satellite — the r05 regression).

Round 5 exited rc=124 with NO one-line JSON ("parsed": null): the harness
timeout struck while a leg hung and the bailout handler wasn't armed yet.
The guards install at module import — BEFORE the first leg — so a forced
hang still prints the headline line: SIGALRM at the budget edge,
SIGTERM/SIGINT from the harness's first strike. The line is evidence, not
success: every bail-out exits NON-ZERO (ISSUE 21). `BENCH_SELFTEST_HANG=1`
simulates the hang without touching jax, keeping this tier-1 fast.
"""

import json
import os
import signal
import subprocess
import sys
import time

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench.py")


def _env(**extra):
    env = dict(os.environ)
    env.update({"BENCH_SELFTEST_HANG": "1", "JAX_PLATFORMS": "cpu"})
    env.update({k: str(v) for k, v in extra.items()})
    return env


def _json_line(stdout: str) -> dict:
    for ln in stdout.splitlines():
        if ln.startswith("{"):
            return json.loads(ln)
    raise AssertionError(f"no JSON line in output: {stdout!r}")


def test_sigalrm_budget_edge_emits_json_on_hang():
    """A leg hung past the whole budget: the import-time SIGALRM guard
    prints the line instead of dying silently at rc=124 — and exits
    non-zero, because a run that bailed out did not succeed."""
    out = subprocess.run(
        [sys.executable, BENCH],
        env=_env(BENCH_TIME_BUDGET="1", BENCH_ALARM_MARGIN="1"),
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 1, out.stderr[-500:]
    line = _json_line(out.stdout)
    assert "error" in line
    assert "budget" in line["error"] or "signal" in line["error"]


def test_sigterm_first_strike_emits_json_on_hang():
    """The harness timeout's first strike (SIGTERM) during a hang still
    yields the one-line JSON — rc=124's silent death is unreachable while
    the interpreter can run a signal handler."""
    proc = subprocess.Popen(
        [sys.executable, BENCH],
        env=_env(BENCH_TIME_BUDGET="600"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    time.sleep(2.0)                       # let the guards arm + hang start
    proc.send_signal(signal.SIGTERM)
    stdout, stderr = proc.communicate(timeout=30)
    assert proc.returncode == 1, stderr[-500:]
    line = _json_line(stdout)
    assert "terminated by signal" in line.get("error", "")


def test_tail_latency_keys_survive_forced_timeout():
    """ISSUE 9: the tail-latency headline keys (conc_p99_ms, shed_429s,
    hedged_wins) are seeded into the always-emitted line at import time,
    so a forced timeout mid-run still reports them (null, not absent)."""
    proc = subprocess.Popen(
        [sys.executable, BENCH],
        env=_env(BENCH_TIME_BUDGET="600"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    time.sleep(2.0)
    proc.send_signal(signal.SIGTERM)
    stdout, stderr = proc.communicate(timeout=30)
    assert proc.returncode == 1, stderr[-500:]
    line = _json_line(stdout)
    for key in ("conc_p99_ms", "shed_429s", "hedged_wins",
                # quantized ANN tier (ISSUE 12): same seeded-null contract
                "knn_int8_qps", "knn_pq_qps", "pq_recall_at_10",
                "vector_stack_bytes_f32", "vector_stack_bytes_quantized",
                # chaos harness (ISSUE 14): same seeded-null contract
                "chaos_rounds", "chaos_parity_checks",
                "chaos_invariant_violations",
                # rebalance-under-load (ISSUE 15): same seeded-null
                # contract
                "rebalance_p99_ms", "rebalance_move_s",
                "recovery_throttle_bytes_per_sec", "decider_vetoes",
                # device telemetry flight recorder (ISSUE 16): same
                # seeded-null contract — the flight sidecar rides the
                # emergency line even when a kill lands mid-leg
                "xla_compile_ms_total", "hbm_peak_bytes",
                "lane_decision_counts", "flight",
                # log-analytics observability tier (ISSUE 17): same
                # seeded-null contract
                "sorted_mesh_qps", "sorted_fanout_qps",
                "subagg_mesh_qps", "monitoring_overview_p50_ms",
                # reverse search + script compiler (ISSUE 18): same
                # seeded-null contract
                "percolate_qps", "percolate_matrix_qps",
                "percolate_vs_loop", "script_score_qps",
                "script_vs_decline",
                # pod-scale serving (ISSUE 19): same seeded-null contract
                "pod_qps", "single_pool_qps", "pod_vs_single",
                "dcn_hops_per_query", "exec_lock_waits",
                # watcher alerting tier (ISSUE 20): same contract
                "watcher_evals_per_sec", "watcher_fire_p50_ms",
                "watcher_percolate_rides", "composite_page_qps"):
        assert key in line, f"[{key}] must survive a forced timeout"
        assert line[key] is None       # nothing measured before the kill


def test_guards_installed_before_first_leg():
    """Source-order tripwire: the bailout install happens at module scope
    (before any leg can run), not inside main_engine()."""
    src = open(BENCH).read()
    body = src.split("def _run_all_legs", 1)[0]
    assert "\n_install_bailout()" in body, \
        "_install_bailout() must run at import time, before the first leg"
    assert "SIGALRM" in src
    # per-leg budget enforcement by elapsed-time subtraction
    assert "_arm_leg_alarm" in src.split("def _run_all_legs", 1)[1]


def test_no_virtual_device_numbers_and_no_assumed_ratio():
    """ISSUE 21 tripwire: the pod leg never re-executes itself on virtual
    CPU devices (with fewer than four real devices it reports
    `pod_skipped`), a CPU run's vs_baseline is null rather than 1.0, and
    a leg that raised makes the run exit non-zero."""
    src = open(BENCH).read()
    assert "xla_force_host_platform_device_count" not in src
    assert "BENCH_POD_CHILD" not in src
    assert "pod_skipped" in src
    assert "1.0 for k in ratio_keys" not in src
    tail = src.split("def main_engine", 1)[1].split("def ", 1)[0]
    assert "_FAILED_LEGS" in tail and "sys.exit(1)" in tail
