"""Fleet supervision: heartbeat liveness + automatic restart-from-checkpoint.

The in-process resilience story (retry, divergence rollback, symmetric fault
plans) covers every fault ALL processes can observe together. The remaining
class is asymmetric: one process of a multi-controller job dies or stalls
mid-collective, the survivors block forever inside jax's allgather, and the
job is dead with no process in a position to recover it — SPMD recovery
requires symmetric decisions (RESILIENCE.md). The reference survives this
class with Spark driver restarts (SURVEY.md §5.4); this module is the
TPU-native equivalent: an external supervisor that owns the fleet's process
lifecycle.

:class:`FleetSupervisor` launches the N training processes as subprocesses,
watches two liveness signals, and on any failure kills the survivors and
relaunches the WHOLE fleet — the restarted processes resume from the latest
agreed checkpoint (``_mp_ckpt_latest`` / ``CheckpointManager`` already
enforce pre-agreed resume points), under a bounded restart budget with
exponential backoff and a hard wall-clock deadline.

Liveness signals:

- **exit**: ``Popen.poll`` — any nonzero exit (crash, ``os._exit``,
  OOM-kill) fails the attempt immediately; success is every process
  exiting 0.
- **heartbeat**: each process touches a per-process file
  (``PHOTON_HEARTBEAT_FILE``) at sweep, coordinate-step, and collective
  boundaries (:func:`heartbeat`, threaded through
  ``game/coordinate_descent.py``, ``game/multiprocess.py``,
  ``glm/training.py``, ``parallel/multihost.py`` and the Avro readers). A
  file older than ``heartbeat_timeout_s`` declares the process stalled. A
  long healthy collective does not beat while inside the collective, so
  the timeout must exceed the longest healthy inter-boundary gap — size it
  from the sweep wall, not the step wall.

Every recovery action posts :class:`~photon_ml_tpu.events.TrainingEvent`s
(``supervisor_*``) which the telemetry bridge translates into
``photon_supervisor_*`` metrics, and each launch runs under a
``supervisor.attempt`` span.

This module is the ONLY place in ``photon_ml_tpu/`` allowed to spawn or
signal processes (``tools/check_resilience_hygiene.py`` rule 6): process
lifecycle must stay visible to the supervisor, or a driver-forked child
would be invisible to the restart logic that claims to own recovery.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import subprocess
import sys
import time
from typing import Optional, Sequence

logger = logging.getLogger(__name__)

#: per-process heartbeat file (set by the supervisor; drivers touch it)
HEARTBEAT_ENV = "PHOTON_HEARTBEAT_FILE"
#: where the chief driver writes its result dict as JSON (set by the
#: supervisor so a supervised run can return the same payload a direct
#: driver call returns)
RESULT_ENV = "PHOTON_RESULT_FILE"
#: which supervisor attempt a process belongs to (0 = first launch) —
#: read by FaultSpec.attempts gating and exported for log correlation
RESTART_COUNT_ENV = "PHOTON_RESTART_COUNT"


# ---------------------------------------------------------------------------
# The worker-side hook
# ---------------------------------------------------------------------------


def heartbeat(site: str = "") -> None:
    """Touch this process's heartbeat file (no-op unsupervised).

    Called at sweep/coordinate/collective boundaries in the training hot
    paths; with no ``PHOTON_HEARTBEAT_FILE`` in the environment (the
    production default outside supervised runs) the cost is one environ
    lookup. Never raises: a failing beat must degrade to "supervisor may
    restart us", not kill a healthy training step.
    """
    path = os.environ.get(HEARTBEAT_ENV)
    if not path:
        return
    try:
        os.utime(path, None)
    except OSError:
        try:
            with open(path, "w") as f:
                f.write(site)
        except OSError:
            logger.warning("heartbeat touch failed for %s", path)


def write_result_file(result: dict) -> None:
    """Driver-side: persist the run's result dict where the supervisor
    asked for it (``PHOTON_RESULT_FILE``; no-op unsupervised). Written
    atomically so a kill mid-write cannot hand the supervisor half a
    JSON document."""
    path = os.environ.get(RESULT_ENV)
    if not path:
        return
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f)
    os.replace(tmp, path)


# ---------------------------------------------------------------------------
# The supervisor
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SupervisorPolicy:
    """Restart budget + liveness thresholds.

    ``max_restarts`` bounds RESTARTS (not attempts; 0 = launch once).
    ``heartbeat_timeout_s`` declares a running process stalled when its
    beat file goes this stale (None disables stall detection — exit codes
    only). ``deadline_s`` is the hard wall across ALL attempts including
    backoff sleeps; like :func:`~photon_ml_tpu.resilience.retry.retry`,
    the supervisor never sleeps into a deadline it would then blow.
    """

    max_restarts: int = 2
    heartbeat_timeout_s: Optional[float] = 300.0
    deadline_s: Optional[float] = None
    poll_interval_s: float = 0.2
    grace_s: float = 5.0
    base_backoff_s: float = 0.5
    backoff_multiplier: float = 2.0
    max_backoff_s: float = 30.0

    def __post_init__(self):
        if self.max_restarts < 0:
            raise ValueError(
                f"max_restarts must be >= 0, got {self.max_restarts}")
        if (self.heartbeat_timeout_s is not None
                and self.heartbeat_timeout_s <= 0):
            raise ValueError(
                f"heartbeat_timeout_s must be > 0 or None, "
                f"got {self.heartbeat_timeout_s}")


@dataclasses.dataclass
class FleetResult:
    """One supervised run's outcome: the chief's result payload (when the
    driver wrote one) plus the recovery accounting."""

    restarts: int
    attempts: int
    result: Optional[dict]


class FleetExhaustedError(RuntimeError):
    """The fleet kept failing past its restart budget (or deadline)."""


@dataclasses.dataclass(frozen=True)
class _Fault:
    """What the watch loop observed: ``reason`` is ``"exit"`` (a nonzero
    returncode) or ``"stall"`` (a stale heartbeat)."""

    reason: str
    process: int
    returncode: Optional[int] = None
    heartbeat_age_s: Optional[float] = None


class FleetSupervisor:
    """Launch, watch, and restart one N-process training fleet.

    ``command`` is the argv every process runs (multi-controller SPMD: one
    program). The supervisor adds per-process environment:
    ``PHOTON_PROCESS_ID``, ``PHOTON_HEARTBEAT_FILE``,
    ``PHOTON_RESTART_COUNT``, ``PHOTON_RESULT_FILE`` (chief only) and — at
    ``n_processes > 1`` — ``PHOTON_COORDINATOR_ADDRESS`` /
    ``PHOTON_NUM_PROCESSES`` with a freshly-bound loopback port per
    attempt (re-binding the dead attempt's port would race TIME_WAIT).

    ``run_dir`` receives heartbeat files and per-attempt process logs
    (``attempt-K/proc-I.log``) — the post-mortem surface the exhaustion
    error quotes from.
    """

    def __init__(self, command: Sequence[str], n_processes: int,
                 run_dir: str, policy: SupervisorPolicy = SupervisorPolicy(),
                 *, env: Optional[dict] = None, bus=None):
        if n_processes < 1:
            raise ValueError(f"n_processes must be >= 1, got {n_processes}")
        self.command = list(command)
        self.n_processes = int(n_processes)
        self.run_dir = run_dir
        self.policy = policy
        self.base_env = dict(os.environ if env is None else env)
        if bus is None:
            from photon_ml_tpu.events import GLOBAL_BUS as bus
        self.bus = bus
        self.restarts = 0
        self._procs: list[subprocess.Popen] = []
        self._hb_files: list[str] = []
        self._spawn_t = 0.0

    # --- lifecycle --------------------------------------------------------
    def run(self) -> FleetResult:
        """Supervise to completion. Returns on an all-zero fleet exit;
        raises :class:`FleetExhaustedError` past the restart budget or
        deadline (with the failing processes' log tails in the message)."""
        from photon_ml_tpu.resilience.retry import _sleep
        from photon_ml_tpu.telemetry import tracing

        os.makedirs(self.run_dir, exist_ok=True)
        result_path = os.path.join(self.run_dir, "result.json")
        t0 = time.monotonic()
        attempt = 0
        self.bus.post("supervisor_started", processes=self.n_processes,
                      max_restarts=self.policy.max_restarts,
                      command=" ".join(self.command))
        with tracing.span("supervisor.run", processes=self.n_processes):
            while True:
                with tracing.span("supervisor.attempt", attempt=attempt):
                    self._spawn(attempt, result_path)
                    fault = self._watch(t0)
                    if fault is None:
                        self.bus.post("supervisor_completed",
                                      attempts=attempt + 1,
                                      restarts=self.restarts,
                                      elapsed_s=time.monotonic() - t0)
                        return FleetResult(
                            restarts=self.restarts, attempts=attempt + 1,
                            result=self._read_result(result_path))
                    # an installed flight recorder (telemetry.flightrec)
                    # treats reason="stall" as a black-box dump trigger —
                    # the last spans/events/history hit disk before the
                    # stalled fleet is killed and restarted below
                    self.bus.post(
                        "supervisor_fault_detected", attempt=attempt,
                        reason=fault.reason, process=fault.process,
                        returncode=fault.returncode,
                        heartbeat_age_s=fault.heartbeat_age_s)
                    logger.warning(
                        "fleet fault (attempt %d): %s on process %d "
                        "(rc=%s, heartbeat age %s)", attempt, fault.reason,
                        fault.process, fault.returncode,
                        fault.heartbeat_age_s)
                    self._kill_fleet()
                backoff = min(
                    self.policy.base_backoff_s
                    * self.policy.backoff_multiplier ** attempt,
                    self.policy.max_backoff_s)
                elapsed = time.monotonic() - t0
                over_deadline = (
                    self.policy.deadline_s is not None
                    and elapsed + backoff >= self.policy.deadline_s)
                if attempt >= self.policy.max_restarts or over_deadline:
                    self.bus.post("supervisor_exhausted",
                                  attempts=attempt + 1,
                                  restarts=self.restarts,
                                  deadline_hit=over_deadline,
                                  elapsed_s=elapsed)
                    raise FleetExhaustedError(
                        f"fleet failed {attempt + 1} time(s) over "
                        f"{elapsed:.1f}s ({fault.reason} on process "
                        f"{fault.process}"
                        + (f", rc={fault.returncode}"
                           if fault.returncode is not None else "")
                        + (f"; deadline {self.policy.deadline_s}s hit"
                           if over_deadline else
                           f"; restart budget {self.policy.max_restarts} "
                           f"spent")
                        + f"); last logs:\n"
                        + self._log_tails(attempt))
                self.restarts += 1
                self.bus.post("supervisor_restart", attempt=attempt + 1,
                              backoff_s=backoff, reason=fault.reason)
                _sleep(backoff)
                attempt += 1

    # --- internals --------------------------------------------------------
    def _spawn(self, attempt: int, result_path: str) -> None:
        port = _free_loopback_port() if self.n_processes > 1 else None
        attempt_dir = os.path.join(self.run_dir, f"attempt-{attempt}")
        os.makedirs(attempt_dir, exist_ok=True)
        self._procs, self._hb_files = [], []
        self._spawn_t = time.monotonic()
        for pid in range(self.n_processes):
            hb = os.path.join(self.run_dir, f"proc-{pid}.heartbeat")
            # pre-touch so staleness counts from spawn, with no
            # missing-file special case in the watch loop
            with open(hb, "w") as f:
                f.write(f"attempt-{attempt}")
            env = dict(self.base_env)
            env["PHOTON_PROCESS_ID"] = str(pid)
            env[RESTART_COUNT_ENV] = str(attempt)
            env[HEARTBEAT_ENV] = hb
            if pid == 0:
                env[RESULT_ENV] = result_path
            else:
                env.pop(RESULT_ENV, None)
            if port is not None:
                env["PHOTON_COORDINATOR_ADDRESS"] = f"localhost:{port}"
                env["PHOTON_NUM_PROCESSES"] = str(self.n_processes)
            log = open(os.path.join(attempt_dir, f"proc-{pid}.log"), "w")
            try:
                proc = subprocess.Popen(
                    self.command, env=env, stdout=log,
                    stderr=subprocess.STDOUT,
                    start_new_session=True)
            finally:
                log.close()  # the child holds its own descriptor
            self._procs.append(proc)
            self._hb_files.append(hb)

    def _watch(self, t0: float) -> Optional[_Fault]:
        """Block until the attempt resolves: None on all-zero exit, a
        :class:`_Fault` on the first nonzero exit or stale heartbeat.
        Raises :class:`FleetExhaustedError` straight away on deadline —
        a deadline admits no further restart."""
        from photon_ml_tpu.resilience.retry import _sleep

        while True:
            rcs = [p.poll() for p in self._procs]
            for pid, rc in enumerate(rcs):
                if rc is not None and rc != 0:
                    return _Fault(reason="exit", process=pid, returncode=rc)
            if all(rc == 0 for rc in rcs):
                return None
            if self.policy.heartbeat_timeout_s is not None:
                now = time.time()
                for pid, rc in enumerate(rcs):
                    if rc is not None:
                        continue  # already exited 0; no beats expected
                    try:
                        age = now - os.stat(self._hb_files[pid]).st_mtime
                    except OSError:
                        age = time.monotonic() - self._spawn_t
                    if age > self.policy.heartbeat_timeout_s:
                        return _Fault(reason="stall", process=pid,
                                      heartbeat_age_s=age)
            if (self.policy.deadline_s is not None
                    and time.monotonic() - t0 > self.policy.deadline_s):
                self._kill_fleet()
                self.bus.post("supervisor_exhausted",
                              attempts=self.restarts + 1,
                              restarts=self.restarts, deadline_hit=True,
                              elapsed_s=time.monotonic() - t0)
                raise FleetExhaustedError(
                    f"fleet ran past the {self.policy.deadline_s}s "
                    f"deadline; killed. Last logs:\n"
                    + self._log_tails(self.restarts))
            _sleep(self.policy.poll_interval_s)

    def _kill_fleet(self) -> None:
        """SIGTERM every survivor, grace, then SIGKILL — survivors are
        typically blocked inside a collective and cannot exit on their
        own (that inability is the fault class this module exists for)."""
        from photon_ml_tpu.resilience.retry import _sleep

        for p in self._procs:
            if p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass
        deadline = time.monotonic() + self.policy.grace_s
        while (any(p.poll() is None for p in self._procs)
               and time.monotonic() < deadline):
            _sleep(min(0.05, self.policy.poll_interval_s))
        for p in self._procs:
            if p.poll() is None:
                try:
                    p.kill()
                except OSError:
                    pass
                p.wait()

    def _read_result(self, path: str) -> Optional[dict]:
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, json.JSONDecodeError):
            return None

    def _log_tails(self, attempt: int, n_bytes: int = 2000) -> str:
        out = []
        for pid in range(self.n_processes):
            path = os.path.join(self.run_dir, f"attempt-{attempt}",
                                f"proc-{pid}.log")
            try:
                with open(path, "rb") as f:
                    f.seek(0, os.SEEK_END)
                    f.seek(max(0, f.tell() - n_bytes))
                    tail = f.read().decode("utf-8", "replace")
            except OSError:
                tail = "<no log>"
            out.append(f"--- process {pid} ({path}) ---\n{tail}")
        return "\n".join(out)


def _free_loopback_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# Driver integration (the CLI --supervise path)
# ---------------------------------------------------------------------------

#: value-taking supervision flags stripped from the worker command (the
#: workers must TRAIN, not recursively supervise)
_SUPERVISION_FLAGS = ("--supervise", "--max-restarts",
                      "--heartbeat-timeout-s", "--restart-deadline-s")


def strip_supervision_flags(argv: Sequence[str]) -> list[str]:
    out: list[str] = []
    skip = False
    for a in argv:
        if skip:
            skip = False
            continue
        if a in _SUPERVISION_FLAGS:
            skip = True
            continue
        if any(a.startswith(f + "=") for f in _SUPERVISION_FLAGS):
            continue
        out.append(a)
    return out


def _workers_would_open_tpu() -> bool:
    """Whether worker processes started on this host would open a TPU
    backend — decided WITHOUT touching JAX (a supervisor that initialized
    the backend would hold the chip its workers need): JAX picks the TPU
    whenever libtpu is installed, unless ``JAX_PLATFORMS`` names other
    platforms only."""
    import importlib.util

    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return False
    return importlib.util.find_spec("libtpu") is not None


def supervise_from_args(driver: str, raw_argv: Sequence[str], args,
                        *, worker_flags: Sequence[str] = ()) -> dict:
    """The drivers' ``--supervise N`` entry point: relaunch THIS command
    (minus the supervision flags, plus ``worker_flags`` — e.g.
    ``--checkpoint --resume --multihost``) as an N-process supervised
    fleet and return the chief's result dict with a ``restarts`` count
    added."""
    if args.supervise > 1 and _workers_would_open_tpu():
        raise SystemExit(
            f"--supervise {args.supervise} starts {args.supervise} worker "
            f"processes on this host and assigns them no devices: on a TPU "
            f"host each would claim every chip, and all but one would fail "
            f"or hang. Use --supervise 1 (one process drives all local "
            f"chips; shard with --mesh), or set JAX_PLATFORMS=cpu for a "
            f"CPU fleet.")
    command = [sys.executable, "-m", "photon_ml_tpu", driver]
    command += strip_supervision_flags(raw_argv)
    for f in worker_flags:
        if f not in command:
            command.append(f)
    hb = args.heartbeat_timeout_s
    policy = SupervisorPolicy(
        max_restarts=args.max_restarts,
        heartbeat_timeout_s=(hb if hb and hb > 0 else None),
        deadline_s=args.restart_deadline_s)
    sup = FleetSupervisor(command, args.supervise,
                          os.path.join(args.output_dir, "supervisor"),
                          policy)
    fleet = sup.run()
    out = dict(fleet.result or {})
    out.setdefault("output_dir", args.output_dir)
    out["restarts"] = fleet.restarts
    return out
