"""Multi-process launcher for the TCP backend.

Reference analog: the ctest harness launches "multi-node" tests as
``mpiexec -np N`` on one node (``/root/reference/CMakeLists.txt:967-983``).
Here the launcher spawns N Python processes, hands each a rank via the
environment, and lets them rendezvous through a shared directory; it works
unchanged across hosts when ``rendezvous_dir`` sits on a shared filesystem
or an explicit ``host:port`` peer list is given.

Ranks started here are **CPU-device ranks**.  A chip belongs to one
process, and children inheriting the parent's environment would each
claim every chip of the host — so the launcher refuses to start unless
the children's environment pins ``JAX_PLATFORMS=cpu``.  One process
drives several chips through the in-process form instead
(:func:`parsec_tpu.multirank.run_multirank_perf`: one ``Context`` per
rank over ``InprocFabric``, rank r on ``jax.local_devices()[r]``).
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
from typing import Dict, List, Optional, Sequence


def launch(
    nranks: int,
    argv: Sequence[str],
    *,
    rendezvous_dir: Optional[str] = None,
    env: Optional[Dict[str, str]] = None,
    timeout: float = 300.0,
    python: Optional[str] = None,
) -> List[subprocess.CompletedProcess]:
    """Run ``python argv...`` once per rank; returns per-rank results.

    Raises on nonzero exit (with the failing rank's stderr attached),
    and before starting anything when the ranks could reach an
    accelerator (see the module docstring).
    """
    platforms = {**os.environ, **(env or {})}.get("JAX_PLATFORMS", "")
    if platforms.strip().lower() != "cpu":
        raise RuntimeError(
            f"comm.launch: refusing to start {nranks} device ranks "
            f"(JAX_PLATFORMS={platforms!r}): every child would claim every "
            "chip of this host.  Multi-process ranks are CPU-device only — "
            "set JAX_PLATFORMS=cpu (env= or the environment); to drive "
            "several chips use one process with "
            "parsec_tpu.multirank.run_multirank_perf")
    rdv = rendezvous_dir or tempfile.mkdtemp(prefix="parsec_tpu_rdv_")
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    procs = []
    for r in range(nranks):
        child_env = dict(os.environ)
        prev = child_env.get("PYTHONPATH")
        child_env["PYTHONPATH"] = pkg_root + (os.pathsep + prev if prev else "")
        child_env.update(env or {})
        child_env.update({
            "PARSEC_TPU_RANK": str(r),
            "PARSEC_TPU_NRANKS": str(nranks),
            "PARSEC_TPU_RDV": rdv,
        })
        procs.append(subprocess.Popen(
            [python or sys.executable, *argv],
            env=child_env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    import time as _time

    deadline = _time.monotonic() + timeout  # one job-wide deadline, not per rank
    results = []
    failed = []
    for r, p in enumerate(procs):
        if failed:  # a failed rank dooms the collective job; reap the rest fast
            for q in procs:
                if q.poll() is None:
                    q.kill()
        try:
            out, err = p.communicate(timeout=max(0.1, deadline - _time.monotonic()))
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
            failed.append((r, "timeout", out, err))
            continue
        results.append(subprocess.CompletedProcess(p.args, p.returncode, out, err))
        if p.returncode != 0:
            failed.append((r, p.returncode, out, err))
    if failed:
        msgs = "\n".join(
            f"--- rank {r} ({why}) ---\nstdout:\n{out}\nstderr:\n{err[-4000:]}"
            for r, why, out, err in failed)
        raise RuntimeError(f"{len(failed)}/{nranks} ranks failed:\n{msgs}")
    return results


def main(argv: Optional[Sequence[str]] = None) -> int:
    """CLI: ``python -m parsec_tpu.comm.launch -n 4 app.py [args...]`` —
    the ``mpiexec -np N`` analogue. Streams each rank's output after the
    job completes, prefixed with its rank."""
    import argparse

    p = argparse.ArgumentParser(
        prog="parsec_tpu.comm.launch",
        description="run a script as N communicating ranks (mpiexec analogue)")
    p.add_argument("-n", "--np", dest="nranks", type=int, required=True,
                   help="number of ranks")
    p.add_argument("--rdv", help="rendezvous directory (shared fs for "
                   "multi-host); default: a fresh temp dir")
    p.add_argument("--timeout", type=float, default=300.0,
                   help="job-wide timeout in seconds")
    p.add_argument("argv", nargs=argparse.REMAINDER,
                   help="script and its arguments")
    args = p.parse_args(argv)
    if not args.argv:
        p.error("no script given")
    # strip only a LEADING "--" (argparse REMAINDER separator); later "--"
    # tokens belong to the launched script's own argument parsing
    cmd = args.argv[1:] if args.argv[0] == "--" else list(args.argv)
    if not cmd:
        p.error("no script given")
    try:
        results = launch(args.nranks, cmd, rendezvous_dir=args.rdv,
                         timeout=args.timeout)
    except RuntimeError as e:
        print(e, file=sys.stderr)
        return 1
    for r, res in enumerate(results):
        for line in (res.stdout or "").splitlines():
            print(f"[rank {r}] {line}")
        for line in (res.stderr or "").splitlines():
            print(f"[rank {r}] {line}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
