"""Pre-fork ``SO_REUSEPORT`` multi-process serving.

One ``ThreadingHTTPServer`` process is GIL-bound: every handler thread,
the dispatch loop and the Python halves of match/serialise share one
interpreter, so a multi-core box serves at roughly one core's
throughput. This module is the process-per-core multiplier
(``REPORTER_TPU_SERVICE_PROCS`` / ``--procs N``): the parent forks N
workers, each binds the SAME ``(host, port)`` with ``SO_REUSEPORT``
(server.ReusePortThreadingHTTPServer) and the kernel spreads accepted
connections across them — no shared accept lock, no proxy hop, and each
worker owns a whole interpreter, dispatcher and device handle.

Fork discipline — everything heavyweight happens POST-fork:

- the parent calls :func:`serve_prefork` with a ``make_service``
  thunk and never builds a matcher, device handle or dispatcher itself;
  each worker runs the thunk after the fork, so no child ever inherits
  a native WorkerPool, a JAX client or a live dispatcher thread
  (native/__init__.py's ``_check_owner`` makes the inherited-handle
  mistake loud rather than a condvar hang);
- module singletons that DO predate the fork (metrics registry,
  TrackedLock internals, flight-recorder ring, spool caches, racecheck
  graphs) are reset in the child by the :mod:`..utils.forksafe` hooks,
  so each worker's /metrics and postmortems describe its own work.

Per-process writer identity: each worker slot extends
``REPORTER_TPU_WRITER_ID`` with ``p<slot>`` before building its
service, so every epoch tile file name (streaming/anonymiser.py
``{source}.{writer}.e{epoch:08d}``) and therefore every ingest-ledger
key (datastore/ingest.py) is process-unique — tee/egress stays
exactly-once across workers exactly as it does across bigreplay's
multi-writer topology. A restarted worker reuses its slot's id: PR 9's
committed-epoch markers make the re-emit overwrite byte-identically
instead of colliding.

One process per chip: a worker's libtpu takes every chip of the host
when it first touches JAX, so on a TPU a second worker could only fail
or hang — and the supervisor would restart it in a loop. ``procs > 1``
is therefore CPU serving only: :func:`serve_prefork` refuses to fork
unless the platform is pinned to the CPU (``REPORTER_TPU_PLATFORM=cpu``
or ``JAX_PLATFORMS=cpu``), decided from the environment alone — the
parent never touches JAX. On a TPU host, serve one process per chip.

Supervision: the parent is a dumb waitpid loop — restart a dead worker
in the same slot, forever, with exponential backoff against crash
loops. An rc-137 exit (SIGKILL, or the crash failpoint's ``os._exit
(137)``) is logged as such and the worker's flight-recorder dumps
(``flightrec-<pid>-*``, named by the DEAD pid) are enumerated, never
touched: the postmortem outlives the process it describes. SIGTERM /
SIGINT to the parent TERMs every worker and reaps them; worker exits
during shutdown do not restart.
"""
from __future__ import annotations

import errno
import glob
import logging
import os
import signal
import sys
import time
from typing import Callable, Dict, Optional

logger = logging.getLogger("reporter_tpu.prefork")

ENV_PROCS = "REPORTER_TPU_SERVICE_PROCS"

#: consecutive fast-crash backoff ceiling (seconds); the first restart
#: in a slot is immediate, a crash-looping slot converges to this pace
MAX_BACKOFF_S = 5.0
#: a worker that lived at least this long resets its slot's crash count
HEALTHY_AGE_S = 10.0


def cpu_pinned() -> bool:
    """True when this process tree is pinned to the CPU backend by its
    environment (no JAX import: the pre-fork parent must stay off it)."""
    from ..utils.runtime import ENV_PLATFORM
    if (os.environ.get(ENV_PLATFORM) or "").strip().lower() == "cpu":
        return True
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    return first.strip().lower() == "cpu"


def writer_id_for_slot(slot: int, base: Optional[str] = None) -> str:
    """The slot's writer identity: the inherited ``REPORTER_TPU_
    WRITER_ID`` (multihost deployments already tag each host) extended
    with ``p<slot>`` — stable across restarts of the slot, distinct
    across slots, so epoch tile names and ingest-ledger keys never
    collide between workers sharing a sink."""
    if base is None:
        base = os.environ.get("REPORTER_TPU_WRITER_ID", "")
    return f"{base}.p{slot}" if base else f"p{slot}"


def worker_main(slot: int, make_service: Callable[[], object],
                host: str, port: int,
                procs: Optional[int] = None) -> int:
    """One worker's whole life, run just after the fork: adopt the
    slot's writer identity AND device slice, build the service (device
    handle, native runtime, dispatcher — all POST-fork), bind the
    shared port with ``SO_REUSEPORT`` and serve until TERMed. Returns
    an exit code (the caller ``os._exit``\\ s it — a worker must never
    fall back into the parent's stack)."""
    os.environ["REPORTER_TPU_WRITER_ID"] = writer_id_for_slot(slot)
    # slot-derived device ownership (the writer-identity pattern, for
    # devices): worker s of P claims its contiguous block of
    # jax.local_devices() via REPORTER_TPU_DEVICE_SLICE, so N processes
    # x M devices compose — every worker's decode mesh spans ITS
    # devices and no two workers contend on one device queue. An
    # operator-set slice wins (heterogeneous pinning); single-proc mode
    # claims nothing.
    if procs and procs > 1 and not os.environ.get(
            "REPORTER_TPU_DEVICE_SLICE"):
        os.environ["REPORTER_TPU_DEVICE_SLICE"] = f"{slot}/{procs}"
    # the parent's supervisor handlers are not ours: TERM must close
    # the listener and exit this process, not set the parent's flag
    httpd_box: Dict[str, object] = {}

    def _term(signum, frame):
        srv = httpd_box.get("httpd")
        if srv is not None:
            # shutdown() BLOCKS until serve_forever exits — and this
            # handler runs in the very thread serve_forever occupies,
            # so calling it inline would deadlock the worker against
            # itself. A helper thread lets the handler return, the
            # loop notice the flag, and in-flight requests finish.
            import threading
            threading.Thread(target=srv.shutdown,  # type: ignore
                             daemon=True).start()
        else:
            os._exit(0)

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, signal.SIG_IGN)  # parent owns ^C

    from ..utils import metrics
    from .server import make_server
    service = make_service()
    # response identity header + chaos-harness observability
    service.proc_tag = f"p{slot}:{os.getpid()}"  # type: ignore[attr-defined]
    metrics.count("service.procs.worker_start")
    httpd = make_server(service, host, port, reuse_port=True)
    httpd_box["httpd"] = httpd
    logger.info("prefork worker p%d (pid %d) serving on %s:%d",
                slot, os.getpid(), host, port)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        try:
            httpd.server_close()
        except Exception:
            pass
    return 0


def _exit_code(status: int) -> int:
    """waitpid status -> shell-style exit code (signal n => 128+n)."""
    if os.WIFSIGNALED(status):
        return 128 + os.WTERMSIG(status)
    if os.WIFEXITED(status):
        return os.WEXITSTATUS(status)
    return 1


def _flightrec_dumps(pid: int) -> list:
    """The dead worker's preserved flight-recorder postmortems (named
    by ITS pid — obs/flightrec.py ``flightrec-<pid>-<seq>-*``)."""
    from ..obs import flightrec
    root = flightrec.dump_dir()
    if not root:
        return []
    try:
        return sorted(glob.glob(os.path.join(root, f"flightrec-{pid}-*")))
    except Exception:
        return []


def serve_prefork(make_service: Callable[[], object], host: str,
                  port: int, procs: int,
                  max_total_restarts: Optional[int] = None) -> int:
    """Fork ``procs`` workers sharing (host, port) via ``SO_REUSEPORT``
    and supervise them: restart-on-crash with per-slot backoff, rc-137
    aware logging, flight-recorder dumps preserved and enumerated.
    Blocks until SIGTERM/SIGINT, then TERMs and reaps every worker.
    ``max_total_restarts`` bounds the restart budget (tests/CI; None =
    supervise forever). Returns a process exit code."""
    procs = max(1, int(procs))
    if procs > 1 and not cpu_pinned():
        msg = (f"--procs {procs} refused: pre-fork workers would share one "
               "accelerator, and a chip belongs to one process. Serve one "
               "process per chip, or set REPORTER_TPU_PLATFORM=cpu for "
               "multi-process CPU serving.")
        logger.error("prefork: %s", msg)
        sys.stderr.write(msg + "\n")
        return 2
    shutting_down = {"flag": False}

    def _stop(signum, frame):
        shutting_down["flag"] = True

    old_term = signal.signal(signal.SIGTERM, _stop)
    old_int = signal.signal(signal.SIGINT, _stop)

    slot_of: Dict[int, int] = {}           # pid -> slot
    started_at: Dict[int, float] = {}      # pid -> monotonic start
    crashes: Dict[int, int] = {}           # slot -> consecutive fast crashes
    respawn_at: Dict[int, float] = {}      # slot -> earliest respawn time
    restarts = 0

    def _spawn(slot: int) -> int:
        pid = os.fork()
        if pid == 0:
            # child: never unwind into the supervisor's stack
            code = 1
            try:
                code = worker_main(slot, make_service, host, port,
                                   procs=procs)
            except BaseException:
                logger.exception("prefork worker p%d died in startup",
                                 slot)
            finally:
                os._exit(code)
        slot_of[pid] = slot
        started_at[pid] = time.monotonic()
        logger.info("prefork: started worker p%d as pid %d", slot, pid)
        return pid

    from ..utils import metrics
    for slot in range(procs):
        _spawn(slot)
        metrics.count("service.procs.spawned")

    rc = 0
    try:
        # WNOHANG poll rather than a blocking waitpid: PEP 475 restarts
        # a blocking waitpid after the SIGTERM handler returns, so the
        # shutdown flag would never be seen until a child happened to die
        while (slot_of or respawn_at) and not shutting_down["flag"]:
            # due backed-off respawns first: the backoff is a DEADLINE,
            # never an inline sleep — a crash-looping slot must not
            # stall reaping of other workers or SIGTERM shutdown
            now = time.monotonic()
            for slot in [s for s, at in respawn_at.items() if at <= now]:
                del respawn_at[slot]
                restarts += 1
                metrics.count("service.procs.restarts")
                _spawn(slot)
            try:
                pid, status = os.waitpid(-1, os.WNOHANG)
            except OSError as e:
                if e.errno == errno.ECHILD:
                    if not respawn_at:
                        break
                    time.sleep(0.05)
                    continue
                raise
            if pid == 0:
                time.sleep(0.05)
                continue
            slot = slot_of.pop(pid, None)
            if slot is None:
                continue  # transient fork-exec child (subprocess etc.)
            age = time.monotonic() - started_at.pop(pid, time.monotonic())
            code = _exit_code(status)
            if shutting_down["flag"]:
                logger.info("prefork: worker p%d (pid %d) exited rc %d "
                            "during shutdown", slot, pid, code)
                continue
            metrics.count("service.procs.deaths")
            dumps = _flightrec_dumps(pid)
            if code == 137:
                # SIGKILL-grade: OOM killer, chaos harness, operator.
                # The postmortem is the flight recorder's, not ours.
                logger.error(
                    "prefork: worker p%d (pid %d) SIGKILLed (rc 137) "
                    "after %.1fs; %d flight-recorder dump(s) preserved%s",
                    slot, pid, age, len(dumps),
                    ": " + ", ".join(dumps) if dumps else "")
            else:
                logger.error(
                    "prefork: worker p%d (pid %d) exited rc %d after "
                    "%.1fs%s", slot, pid, code, age,
                    "; dumps: " + ", ".join(dumps) if dumps else "")
            if max_total_restarts is not None \
                    and restarts >= max_total_restarts:
                logger.error("prefork: restart budget exhausted; "
                             "shutting down")
                rc = 1
                shutting_down["flag"] = True
                continue
            # backoff against a crash-looping slot; a worker that
            # served healthily resets its slot's streak. The respawn is
            # SCHEDULED (picked up at the top of the loop), keeping the
            # supervisor responsive for other deaths and for shutdown
            crashes[slot] = 0 if age >= HEALTHY_AGE_S \
                else crashes.get(slot, 0) + 1
            delay = min(MAX_BACKOFF_S, 0.1 * (2 ** crashes[slot])) \
                if crashes[slot] else 0.0
            respawn_at[slot] = time.monotonic() + delay
    finally:
        # TERM + reap every survivor, restore the old handlers
        for pid in list(slot_of):
            try:
                os.kill(pid, signal.SIGTERM)
            except ProcessLookupError:
                pass
        deadline = time.monotonic() + 10.0
        for pid in list(slot_of):
            try:
                while time.monotonic() < deadline:
                    done, _ = os.waitpid(pid, os.WNOHANG)
                    if done == pid:
                        break
                    time.sleep(0.05)
                else:
                    os.kill(pid, signal.SIGKILL)
                    os.waitpid(pid, 0)
            except (ChildProcessError, ProcessLookupError):
                pass
            slot_of.pop(pid, None)
        signal.signal(signal.SIGTERM, old_term)
        signal.signal(signal.SIGINT, old_int)
    logger.info("prefork: supervisor exiting rc %d (%d restarts)",
                rc, restarts)
    return rc


__all__ = ["serve_prefork", "worker_main", "writer_id_for_slot",
           "cpu_pinned", "ENV_PROCS"]
