"""2-process jax.distributed smoke test over localhost
(distributed/launch.py; the reference's analog is
tests/book_distribute/notest_recognize_digits_mlp_dist.py:53-58 —
a pserver + trainer pair on localhost).

Spawns two REAL processes, each with 2 virtual CPU devices; they form
one 4-device global mesh and run a data-parallel train step whose
mean-loss all-reduce crosses the process boundary. Skips (not fails)
where subprocess spawning or the coordinator port is unavailable."""

import os
import socket
import subprocess
import sys

import pytest


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_round(repo, worker, env):
    """Run both workers; returns [(proc, output, timed_out)] with the
    output captured even for workers we had to kill."""
    port = _free_port()
    procs = []
    rows = []
    try:
        for pid in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, worker, repo, str(port), str(pid), "2"],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                env=env, text=True))
        for p in procs:
            try:
                out, _ = p.communicate(timeout=240)
                rows.append((p, out, False))
            except subprocess.TimeoutExpired:
                p.kill()
                try:
                    out, _ = p.communicate(timeout=10)
                except Exception:
                    out = "<no output captured>"
                rows.append((p, out, True))
        return rows
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def test_two_process_global_mesh_all_reduce():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = os.path.join(repo, "tests", "launch_worker.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    # one retry when any worker fails on its own (e.g. the freed
    # coordinator port raced away between _free_port() and bind —
    # typically one worker exits fast and its PEER blocks, so a mixed
    # fail+timeout round is a failure round, not a timeout round)
    rows = None
    for attempt in range(2):
        rows = _spawn_round(repo, worker, env)
        if all(p.returncode == 0 for p, _, _ in rows):
            break
        self_failed = [p for p, _, timed in rows
                       if not timed and p.returncode != 0]
        if not self_failed:
            pytest.skip("distributed workers timed out "
                        "(coordinator blocked in this env)")
        if any("Multiprocess computations aren't implemented" in out
               for _, out, _ in rows):
            # this jaxlib's CPU backend cannot run cross-process
            # computations at all — environment gap, not a code bug
            pytest.skip("jaxlib CPU backend lacks multiprocess "
                        "computation support")
    for pid, (p, out, timed) in enumerate(rows):
        assert p.returncode == 0, "worker %d %s:\n%s" % (
            pid, "timed out" if timed else "failed", out)
    outs = [out for _, out, _ in rows]
    for pid, out in enumerate(outs):
        assert "WORKER_OK %d" % pid in out, out
    # both processes computed the SAME replicated global loss
    l0 = [ln for ln in outs[0].splitlines() if "WORKER_OK" in ln][0]
    l1 = [ln for ln in outs[1].splitlines() if "WORKER_OK" in ln][0]
    assert l0.split("loss=")[1] == l1.split("loss=")[1]
