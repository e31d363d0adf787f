"""Multi-process cloud: Cloud.boot_multihost over 2 jax.distributed
processes — the reference's testMultiNode trick (multiNodeUtils.sh:21-27
launches 4 extra local JVMs to form a real cloud on loopback; here 2 extra
local Python processes form a real 8-device cloud on loopback).
"""

import os
import socket
import subprocess
import sys
import time

import pytest


pytestmark = pytest.mark.slow   # compile-heavy (conftest tier doc)

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _watch_workers(procs, log_paths, deadline_s, stall_s):
    """Bounded watchdog over the worker fleet.  The old sequential
    ``communicate(timeout=...)`` had two failure modes that burned the
    full timeout: a worker that died early left its peer hanging at the
    jax.distributed rendezvous, and a wedged pair produced no output
    until pytest's own timeout with no logs attached.  Poll instead:
    any worker exiting non-zero kills the fleet immediately; no log
    growth within ``stall_s`` (and no exits) means the cloud is wedged
    — kill and fail with every worker's log tail."""
    t0 = time.monotonic()
    last_progress = t0
    sizes = [0] * len(procs)
    alive = len(procs)

    def tails():
        out = []
        for i, lp in enumerate(log_paths):
            try:
                with open(lp, errors="replace") as f:
                    out.append(f"--- worker {i} log tail ---\n"
                               f"{f.read()[-4000:]}")
            except OSError as e:
                out.append(f"--- worker {i} log unreadable: {e} ---")
        return "\n".join(out)

    def kill_all():
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass

    while True:
        now = time.monotonic()
        live = sum(1 for p in procs if p.poll() is None)
        cur = [os.path.getsize(lp) if os.path.exists(lp) else 0
               for lp in log_paths]
        if live < alive or cur != sizes:
            last_progress = now
            alive, sizes = live, cur
        for i, p in enumerate(procs):
            rc = p.poll()
            if rc is not None and rc != 0:
                kill_all()
                pytest.fail(
                    f"worker {i} exited rc={rc} — killed the fleet "
                    f"rather than letting its peer hang at the "
                    f"rendezvous\n{tails()}")
        if live == 0:
            return
        if now - t0 > deadline_s:
            kill_all()
            pytest.fail(f"multihost drill exceeded the "
                        f"{deadline_s:.0f}s global deadline "
                        f"(H2O_TPU_MULTIHOST_DEADLINE_SECS)\n{tails()}")
        if now - last_progress > stall_s:
            kill_all()
            pytest.fail(f"no worker output or exit for {stall_s:.0f}s "
                        f"(H2O_TPU_MULTIHOST_STALL_SECS) — cloud "
                        f"wedged\n{tails()}")
        time.sleep(0.5)


def test_boot_multihost_two_processes(tmp_path):
    port = _free_port()
    coordinator = f"127.0.0.1:{port}"
    worker = os.path.join(os.path.dirname(__file__),
                          "multihost_worker.py")
    env = dict(os.environ)
    # each worker forces its own 4-device CPU platform
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    # stdout is a log file now, not a pipe: defeat block buffering so
    # the stall detector sees progress as it happens
    env["PYTHONUNBUFFERED"] = "1"
    deadline_s = float(os.environ.get(
        "H2O_TPU_MULTIHOST_DEADLINE_SECS", 540))
    stall_s = float(os.environ.get(
        "H2O_TPU_MULTIHOST_STALL_SECS", 240))
    log_paths = [str(tmp_path / f"worker{pid}.log") for pid in range(2)]
    logs = [open(lp, "w") for lp in log_paths]
    try:
        procs = [subprocess.Popen(
            [sys.executable, worker, coordinator, "2", str(pid)],
            stdout=logs[pid], stderr=subprocess.STDOUT,
            env=env, cwd=os.path.dirname(os.path.dirname(worker)))
            for pid in range(2)]
        _watch_workers(procs, log_paths, deadline_s, stall_s)
    finally:
        for f in logs:
            f.close()
    outs = []
    for lp in log_paths:
        with open(lp, errors="replace") as f:
            outs.append(f.read())
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, \
            f"worker {pid} failed (rc={p.returncode}):\n{out[-4000:]}"
        assert f"[p{pid}] MULTIHOST_OK" in out, out[-4000:]
        assert f"[p{pid}] cloud formed: 8 nodes over 2 processes" in out
        assert f"[p{pid}] distributed GBM ok" in out
        assert f"[p{pid}] product mesh formed: " \
               "{'nodes': 4, 'model': 2}" in out
        assert f"[p{pid}] DP x TP DeepLearning ok" in out
        assert f"[p{pid}] product-mesh GBM ok" in out
