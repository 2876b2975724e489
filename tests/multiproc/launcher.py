"""Multi-process launcher for the CPU scaling harness (ISSUE 16 part 4).

Spawns N controller processes, each pinned to ONE virtual CPU device
(`--xla_force_host_platform_device_count=1`), joined into a single
N-device global mesh via ``jax.distributed.initialize`` — the
process-per-device layout of a real pod job (one controller per host),
shrunk onto localhost.  This is the cross-PROCESS complement of the
in-process 8-virtual-device mesh the rest of the suite runs on: the
collectives here cross process boundaries, so the zero-host-sync and
O(local) contracts are exercised against a genuinely distributed
runtime, not a shared address space.

Some jaxlib CPU backends cannot run cross-process computations at all
(no Gloo collectives); every such program fails with
:data:`NO_MULTIPROC`.  The launcher detects that and reports a SKIP
instead of a failure — same policy as ``tests/test_multihost.py``.

Standalone (the ci.sh hook):

    python tests/multiproc/launcher.py [nproc]

prints ``MULTIPROC-OK`` on success or ``SKIP: ...`` (exit 0 either
way); any real worker failure exits nonzero with the worker logs.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

# The sentinel jaxlib raises from every cross-process computation on CPU
# backends without cross-process collective support (kept verbatim in
# sync with tests/test_multihost.py).
NO_MULTIPROC = "Multiprocess computations aren't implemented"

SKIP_MESSAGE = ("this jaxlib's CPU backend has no cross-process "
                "computation support; run the multiproc harness on a "
                "backend with cross-process collectives")

# The smoke worker: halo exchange + overlapped-vs-sequential step on the
# cross-process mesh.  Each process owns exactly one device; the global
# grid spans all of them.  The overlapped (`hide_communication`) step
# must serve BITWISE-identical state to the sequential compute+exchange
# composition — the same contract weak_scaling.py's golden row pins on
# the in-process mesh, here crossing real process boundaries.
SMOKE_WORKER = r"""
import os, sys
pid, nproc, port, outdir = (int(sys.argv[1]), int(sys.argv[2]),
                            sys.argv[3], sys.argv[4])
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                           num_processes=nproc, process_id=pid)
import numpy as np, igg
from igg.models import diffusion3d as d3
me, dims, nprocs, coords, mesh = igg.init_global_grid(
    8, 8, 8, periodx=1, periody=1, periodz=1, quiet=True)
assert nprocs == nproc, (nprocs, nproc)
assert me == pid
# 1) Halo-exchange smoke: a coordinate-filled field crosses process
#    boundaries; the gathered global array is checked against the
#    single-controller oracle by the caller.
A = igg.zeros((8, 8, 8))
X, Y, Z = igg.coord_fields(1.0, 1.0, 1.0, A)
A = igg.update_halo(A + X * 10000 + Y * 100 + Z)
gA = igg.gather(A)
# 2) Overlapped step vs sequential composition, bitwise, on the
#    cross-process mesh.
p = d3.Params()
T, Cp = d3.init_fields(p, np.float32)
seq = d3.make_multi_step(2, p, donate=False, use_pallas=False,
                         overlap=False, tune=False)
ov = d3.make_multi_step(2, p, donate=False, use_pallas=False,
                        overlap=True, tune=False)
a, b = seq(T, Cp), ov(T, Cp)
ga, gb = igg.gather(a), igg.gather(b)
if me == 0:
    assert gA is not None
    np.save(os.path.join(outdir, "halo.npy"), np.asarray(gA))
    assert ga is not None and gb is not None
    assert np.array_equal(np.asarray(ga), np.asarray(gb)), \
        "overlapped step diverged from the sequential composition"
    print("MULTIPROC-SMOKE-OK")
else:
    assert ga is None and gb is None
igg.finalize_global_grid()
"""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def spawn(workdir, worker_src: str, *, nproc: int = 2, args=(),
          timeout: float = 240.0):
    """Launch `nproc` single-device controller processes of `worker_src`.

    Each worker receives argv ``(pid, nproc, port, *args)``.  Returns
    ``(logs, skipped)`` — `skipped` is True when the backend cannot run
    cross-process computations at all (:data:`NO_MULTIPROC` in any
    log).  Raises ``RuntimeError`` on worker failure or timeout, with
    the worker logs in the message."""
    port = str(free_port())
    worker = os.path.join(str(workdir), "multiproc_worker.py")
    with open(worker, "w") as f:
        f.write(worker_src)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, PYTHONPATH=repo)
    procs = [subprocess.Popen(
        [sys.executable, worker, str(p), str(nproc), port,
         *map(str, args)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for p in range(nproc)]
    logs = []
    try:
        for p in procs:
            logs.append(p.communicate(timeout=timeout)[0].decode())
    except subprocess.TimeoutExpired:
        # Don't leave orphans holding the coordinator port; surface
        # whatever the workers produced before hanging.
        partial = list(logs)
        for p in procs[len(logs):]:
            p.kill()
            rest, _ = p.communicate()
            partial.append((rest or b"").decode())
        raise RuntimeError("multiproc workers timed out; partial "
                           "output:\n" + "\n---\n".join(partial))
    if any(NO_MULTIPROC in log for log in logs):
        return logs, True
    bad = [(p, log) for p, log in zip(procs, logs) if p.returncode != 0]
    if bad:
        raise RuntimeError("multiproc worker(s) failed:\n"
                           + "\n---\n".join(log for _, log in bad))
    return logs, False


def main(argv) -> int:
    nproc = int(argv[1]) if len(argv) > 1 else 2
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        try:
            logs, skipped = spawn(td, SMOKE_WORKER, nproc=nproc,
                                  args=(td,))
        except RuntimeError as e:
            print(e)
            return 1
        if skipped:
            print("SKIP: " + SKIP_MESSAGE)
            return 0
        assert any("MULTIPROC-SMOKE-OK" in log for log in logs), logs
        print("MULTIPROC-OK")
        return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
