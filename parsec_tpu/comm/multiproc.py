"""N-process harness over the socket fabric — the ``mpiexec -np N`` analog.

Where :func:`~parsec_tpu.comm.multirank.run_multirank` runs ranks as
threads over an in-process fabric, this launcher spawns each rank as its
own OS **process**, connected by the TCP socket fabric
(:mod:`parsec_tpu.comm.socket_fabric`) — genuinely separate interpreters,
address spaces, and GILs, exactly what a multi-host DCN deployment looks
like (set ``PARSEC_TPU_HOSTS`` and launch the same entry on each host).

The body function must be *importable* (``"pkg.module:function"`` or
``"path/to/file.py:function"``) with the ``fn(ctx, rank, nranks) ->
picklable`` signature run_multirank uses.
"""

from __future__ import annotations

import os
import pickle
import shutil
import socket
import subprocess
import sys
import tempfile
from typing import Any


def _free_port_base(nranks: int) -> int:
    """A base port whose whole [base, base+nranks) range binds (probed
    port-by-port; the range cannot be reserved atomically, so callers
    still retry on a lost race)."""
    for _attempt in range(50):
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        base = probe.getsockname()[1]
        probe.close()
        if base + nranks >= 65000:
            continue
        ok = True
        for r in range(nranks):
            s = socket.socket()
            try:
                s.bind(("127.0.0.1", base + r))
            except OSError:
                ok = False
                break
            finally:
                s.close()
        if ok:
            return base
    raise RuntimeError("no free port range found")


def run_multiproc(nranks: int, target: str, timeout: float = 180.0,
                  nb_cores: int = 0, transport: str = "socket",
                  distributed: bool = False) -> list[Any]:
    """Run ``target`` on ``nranks`` subprocess ranks; returns the per-rank
    results.  Retries once on a lost port-range race (a bind collision
    surfaces as one rank failing, or as a timeout of the survivors).

    ``transport``: ``"socket"`` (host-object payloads) or ``"device"`` —
    each rank binds one JAX device, registered payloads live
    device-resident, and GETs land directly on the consumer's device
    (:mod:`parsec_tpu.comm.device_socket`, the deployable DCN tier).

    ``distributed=True`` bootstraps ``jax.distributed`` across the ranks
    first — a coordinator on 127.0.0.1 plus per-rank process ids, the
    exact real-pod path of :func:`~parsec_tpu.comm.device_socket.
    maybe_init_distributed` (each process then sees its local chips; on
    the forced-CPU test backend, its own CPU device).

    Execution is therefore **at-least-once**: on the retry path every rank
    body runs again from scratch, so bodies with external side effects
    (files, network writes) must be idempotent or key their outputs by
    attempt.  The collision happens while the socket fabric bootstraps —
    normally before any user code runs — but a partially-connected mesh can
    have let early ranks start their bodies before the failure surfaced."""
    if transport not in ("socket", "device"):
        raise ValueError(f"unknown transport {transport!r}")
    if distributed and transport != "device":
        # _rank_main bootstraps jax.distributed on the device-transport
        # path only; silently skipping it would fail far from the cause
        raise ValueError("distributed=True requires transport='device'")
    try:
        return _run_multiproc(nranks, target, timeout, nb_cores, transport,
                              distributed)
    except (RuntimeError, TimeoutError) as e:
        if "Address already in use" not in str(e):
            raise
        return _run_multiproc(nranks, target, timeout, nb_cores, transport,
                              distributed)


def _run_multiproc(nranks: int, target: str, timeout: float,
                   nb_cores: int, transport: str = "socket",
                   distributed: bool = False) -> list[Any]:
    # one extra port for the jax.distributed coordinator when asked
    base = _free_port_base(nranks + (1 if distributed else 0))
    tmp = tempfile.mkdtemp(prefix="parsec_mp_")
    env = dict(os.environ)
    # a chip belongs to one process — the parent's, if it touched JAX —
    # so subprocess ranks are plain CPU interpreters.  All ranks are
    # local here, so a leftover multi-host spec must not leak in.
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PARSEC_TPU_HOSTS", None)
    # forward the wire-path comm params the parent may have set
    # in-process (params.set) so every rank agrees on the framing — both
    # ends of a fabric must parse the same wire format (docs/COMM.md).
    # An explicit PARSEC_MCA_* in the caller's environment still wins.
    from ..core.params import params as _p
    for name in ("comm_wire_binary", "comm_get_frag_bytes",
                 "comm_get_window", "comm_socket_buf_bytes",
                 "comm_codec_pickle_fallback", "comm_bcast_tree"):
        env.setdefault(f"PARSEC_MCA_{name}", str(_p.get(name)))
    # forward the autotuner consult knobs the same way: every rank of a
    # fabric must agree on WHETHER (and from which store) a persisted
    # tuning vector applies, or ranks would run different knob vectors.
    # lookup(), not get(): the parent may never have imported tune/
    for name in ("tune_db", "tune_db_path", "tune_adaptive"):
        p = _p.lookup(name)
        if p is not None:
            env.setdefault(f"PARSEC_MCA_{name}", str(p.value))
    env["PARSEC_MP_NRANKS"] = str(nranks)
    env["PARSEC_MP_TARGET"] = target
    env["PARSEC_MP_BASE_PORT"] = str(base)
    env["PARSEC_MP_NB_CORES"] = str(nb_cores)
    env["PARSEC_MP_TIMEOUT"] = str(timeout)
    env["PARSEC_MP_TRANSPORT"] = transport
    if distributed:
        env["PARSEC_TPU_COORDINATOR"] = f"127.0.0.1:{base + nranks}"
        env["PARSEC_TPU_NUM_PROCS"] = str(nranks)
    else:
        env.pop("PARSEC_TPU_COORDINATOR", None)
    procs: list[subprocess.Popen] = []
    logs: list[str] = []
    try:
        for r in range(nranks):
            e = dict(env)
            e["PARSEC_MP_RANK"] = str(r)
            if distributed:
                e["PARSEC_TPU_PROC_ID"] = str(r)
            e["PARSEC_MP_RESULT"] = os.path.join(tmp, f"rank{r}.pkl")
            log = os.path.join(tmp, f"rank{r}.log")
            logs.append(log)
            with open(log, "wb") as lf:
                # per-rank log FILES, not pipes: a chatty rank must never
                # block on a full pipe the parent isn't draining yet
                procs.append(subprocess.Popen(
                    [sys.executable, "-c",
                     "from parsec_tpu.comm.multiproc import _rank_main; "
                     "_rank_main()"],
                    env=e, cwd=os.getcwd(), stdout=lf,
                    stderr=subprocess.STDOUT))
        # one shared deadline, polled: the first nonzero exit kills the
        # survivors immediately (they would otherwise hang waiting for the
        # dead rank's activations until their own timeouts)
        import time as _time
        deadline = _time.monotonic() + timeout
        failed: list[int] = []
        while True:
            codes = [p.poll() for p in procs]
            failed = [r for r, c in enumerate(codes)
                      if c is not None and c != 0]
            if failed or all(c is not None for c in codes):
                break
            if _time.monotonic() > deadline:
                for q in procs:
                    q.kill()
                for q in procs:
                    q.wait()     # reap: no zombies on the timeout path
                hung = [r for r, c in enumerate(codes) if c is None]
                raise TimeoutError(
                    f"rank(s) {hung} did not finish within {timeout}s\n"
                    + _tails(logs))
            _time.sleep(0.05)
        if failed:
            for q in procs:
                q.kill()
            for q in procs:
                q.wait()
            raise RuntimeError(
                f"rank(s) {failed} failed:\n"
                + _tails([logs[r] for r in failed]))
        results: list[Any] = []
        for r in range(nranks):
            with open(os.path.join(tmp, f"rank{r}.pkl"), "rb") as f:
                results.append(pickle.load(f))
        return results
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _tails(logs: list[str], nbytes: int = 2000) -> str:
    out = []
    for log in logs:
        try:
            with open(log, "rb") as f:
                data = f.read()[-nbytes:]
            out.append(f"--- {os.path.basename(log)} ---\n"
                       + data.decode(errors="replace"))
        except OSError:
            pass
    return "\n".join(out)


def _rank_main() -> None:
    """Subprocess entry: build the socket-backed runtime and run the body."""
    # force-CPU before jax can load a TPU plugin (mirrors tests/conftest)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass

    import importlib
    import importlib.util

    from ..runtime.context import Context
    from .remote_dep import RemoteDepEngine
    from .socket_fabric import SocketCommEngine, SocketFabric

    transport = os.environ.get("PARSEC_MP_TRANSPORT", "socket")
    if transport == "device":
        # real-pod hook: with a coordinator configured this initializes
        # jax.distributed so the process sees its local chips
        from .device_socket import maybe_init_distributed
        maybe_init_distributed()

    rank = int(os.environ["PARSEC_MP_RANK"])
    nranks = int(os.environ["PARSEC_MP_NRANKS"])
    base = int(os.environ["PARSEC_MP_BASE_PORT"])
    nb_cores = int(os.environ["PARSEC_MP_NB_CORES"])
    timeout = float(os.environ["PARSEC_MP_TIMEOUT"])
    mod_name, fn_name = os.environ["PARSEC_MP_TARGET"].rsplit(":", 1)
    if mod_name.endswith(".py"):    # file-path form: "dir/bodies.py:fn"
        spec = importlib.util.spec_from_file_location("_mp_target", mod_name)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    else:
        mod = importlib.import_module(mod_name)
    fn = getattr(mod, fn_name)

    fabric = SocketFabric(nranks, rank, base_port=base)
    ctx = Context(nb_cores=nb_cores, nb_ranks=nranks, my_rank=rank)
    if transport == "device":
        from .device_socket import DeviceSocketCommEngine
        ce = DeviceSocketCommEngine(fabric)
    else:
        ce = SocketCommEngine(fabric)
    eng = RemoteDepEngine(ctx, ce)
    ctx.start()
    result = fn(ctx, rank, nranks)
    # context-level drain before teardown (the run_multirank discipline)
    eng.quiesce(timeout=timeout / 2)
    ctx.fini()
    with open(os.environ["PARSEC_MP_RESULT"], "wb") as f:
        pickle.dump(result, f)
