"""Kill drill (ISSUE 4 acceptance): SIGTERM mid-run → drained
checkpoint + exit 75 → run_elastic relaunches with --resume → the
stitched trajectory is bitwise identical to an uninterrupted run.

The worker (tests/preemption_worker.py) is the production CLI round
loop (cli.run_experiment) with a fingerprint callback; the harness is
the real ElasticRunner with an injected popen that lands a SIGTERM on
the first child after its second completed round. Two variants: sync
checkpointing, and --async_checkpoint with writes slowed so one is in
flight at kill time (the drain must still land every queued write
before exiting).
"""
import os
import re
import signal
import subprocess
import sys
import threading

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from fedtorch_tpu.robustness.harness import ElasticRunner  # noqa: E402

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "preemption_worker.py")
_TRAJ = re.compile(r"^(TRAJ round=\d+ .*)$", re.M)
ROUNDS = 6


def _worker_env():
    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [repo_root, env.get("PYTHONPATH", "")])
    return env


def _baseline(ckpt_dir: str):
    """Uninterrupted run — the reference trajectory."""
    out = subprocess.run(
        [sys.executable, _WORKER, "--ckpt", ckpt_dir,
         "--rounds", str(ROUNDS)],
        capture_output=True, text=True, timeout=300, env=_worker_env())
    assert out.returncode == 0, out.stdout + out.stderr
    traj = _TRAJ.findall(out.stdout)
    assert len(traj) == ROUNDS, out.stdout
    return traj


def _drill(ckpt_dir: str, extra_args):
    """Run the worker under ElasticRunner; SIGTERM the FIRST child
    after its second TRAJ line; return (rc, per-child lines, harness
    log)."""
    cmd = [sys.executable, _WORKER, "--ckpt", ckpt_dir,
           "--rounds", str(ROUNDS), "--round_sleep", "0.5"] + extra_args
    outs, logs, readers = [], [], []
    env = _worker_env()

    def popen(c, **kw):
        proc = subprocess.Popen(c, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True,
                                bufsize=1, env=env)
        lines = []
        outs.append(lines)
        kill_this = len(outs) == 1

        def reader():
            for line in proc.stdout:
                lines.append(line.rstrip("\n"))
                if kill_this and sum(
                        1 for ln in lines
                        if ln.startswith("TRAJ")) == 2:
                    try:
                        os.kill(proc.pid, signal.SIGTERM)
                    except ProcessLookupError:  # raced to exit
                        pass

        t = threading.Thread(target=reader, daemon=True)
        t.start()
        readers.append(t)
        return proc

    runner = ElasticRunner(cmd, ckpt_dir=ckpt_dir, max_restarts=3,
                           popen=popen, sleep_fn=lambda s: None,
                           log_fn=logs.append)
    rc = runner.run()
    for t in readers:
        t.join(timeout=30)
    return rc, runner, outs, logs


def _check_drill(baseline, rc, runner, outs, logs):
    assert rc == 0, (outs, logs)
    # exactly one restart: kill -> 75 -> relaunch -> completion
    assert runner.launches == 2, logs
    assert any("exited 75 (restartable)" in ln for ln in logs), logs
    # the first child really drained (not just died)
    assert any(ln.startswith("PREEMPTED") for ln in outs[0]), outs[0]
    # the relaunch carried --resume (a checkpoint existed)
    assert any("--resume" in ln and "launch #2" in ln
               for ln in logs), logs
    stitched = [ln for lines in outs for ln in lines
                if ln.startswith("TRAJ")]
    # no round lost, none repeated, every fingerprint bitwise equal
    assert stitched == baseline, (baseline, stitched)


@pytest.mark.slow
def test_kill_drill_sync_checkpoint(tmp_path):
    baseline = _baseline(str(tmp_path / "base"))
    rc, runner, outs, logs = _drill(str(tmp_path / "drill"), [])
    _check_drill(baseline, rc, runner, outs, logs)


@pytest.mark.slow
def test_kill_drill_async_write_in_flight(tmp_path):
    """--async_checkpoint with every write slowed 0.4s: the kill lands
    with a queued/in-flight write; the drain must flush it AND the
    final checkpoint before exiting 75."""
    baseline = _baseline(str(tmp_path / "base"))
    rc, runner, outs, logs = _drill(
        str(tmp_path / "drill"),
        ["--async_checkpoint", "--slow_writes", "0.4"])
    _check_drill(baseline, rc, runner, outs, logs)
