"""Plain worker processes for the Python-integer arithmetic: sr25519 keys
and signatures in set-up, the reference's lanes after the window.

Each worker is `python3 -m benchmarks.workers`, a process of its own that
reads one pickled job (function name, items) on stdin and writes the
pickled results on stdout. It imports benchmarks.datagen and the reference
and nothing else: never JAX, never the program, so it never touches the
chip the parent holds. The parent starts them, waits for every one and
raises if one failed; nothing outlives map().
"""

from __future__ import annotations

import os
import pickle
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def worker_count() -> int:
    return max(1, min(12, (os.cpu_count() or 2) - 1))


def _functions() -> dict:
    from benchmarks import datagen
    from benchmarks.reference import commit_ref

    return {"sr_keypair": datagen.sr_keypair,
            "sr_sign_many": datagen.sr_sign_many,
            "verify_lane": commit_ref.verify_lane}


def map(function: str, items: list) -> list:  # noqa: A001 - it is one
    """[f(item) for item in items], spread over worker processes."""
    if not items:
        return []
    n = min(worker_count(), len(items))
    bounds = [len(items) * i // n for i in range(n + 1)]
    env = dict(os.environ, PYTHONPATH=ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    procs = [subprocess.Popen(
        [sys.executable, "-m", "benchmarks.workers"], cwd=ROOT, env=env,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE) for _ in range(n)]
    # hand every worker its share before reading any answer: they work at
    # the same time
    try:
        for proc, lo, hi in zip(procs, bounds, bounds[1:]):
            proc.stdin.write(pickle.dumps((function, items[lo:hi])))
            proc.stdin.close()
        out: list = []
        for proc in procs:
            data = proc.stdout.read()
            if proc.wait() != 0:
                raise RuntimeError(f"worker for {function} exited with "
                                   f"{proc.returncode}")
            out.extend(pickle.loads(data))
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
            proc.wait()
            proc.stdout.close()
    return out


if __name__ == "__main__":
    name, todo = pickle.loads(sys.stdin.buffer.read())
    fn = _functions()[name]
    sys.stdout.buffer.write(pickle.dumps([fn(item) for item in todo]))
