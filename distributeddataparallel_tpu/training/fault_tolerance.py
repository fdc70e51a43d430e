"""Fault tolerance: preemption-safe checkpoint IO, step watchdog, and
numerical circuit breaking.

The reference dies with its first fault — any worker crash loses all
training state (SURVEY.md §2d.5), and the pjit/TPUv4 scaling report
treats preemption recovery as a first-class requirement at pod scale.
This module is the recovery half of that story (``utils.chaos`` is the
injection half that proves it works):

- ``ResilientCheckpointer`` — ``training.checkpoint.Checkpointer`` with
  every save wrapped in bounded retry (exponential backoff + jitter),
  post-save atomic-write verification, and restore-side
  corrupt/partial-checkpoint detection that quarantines the bad step and
  falls back to the newest intact one instead of crashing.
- ``StepWatchdog`` — a wall-clock deadline on train-loop heartbeats; a
  wedged collective stops the heartbeats, the watchdog logs a diagnostic
  with the last-known loop state and forces checkpoint-then-exit (exit
  code 75 = EX_TEMPFAIL) instead of hanging forever, so launcher
  supervision can restart from the last checkpoint.
- ``NonFiniteBreaker`` — the host-side half of the train step's
  ``nonfinite_guard``: counts consecutive skipped steps and aborts with
  a clear error once the run is diverging rather than glitching.

Together with ``runtime.launcher.spawn(max_restarts=...)`` these close
the loop: crash -> supervised restart -> elastic resume from the newest
intact checkpoint.
"""

from __future__ import annotations

import os
import random
import threading
import time
from typing import Any, Callable

import jax

from distributeddataparallel_tpu.training.checkpoint import Checkpointer
from distributeddataparallel_tpu.utils.logging import warn_all

Pytree = Any

#: EX_TEMPFAIL — the watchdog's exit code: "transient failure, retry me".
#: Distinct from ordinary crashes so operators can tell a hang-kill from
#: a bug in the exit-code stream; launcher supervision restarts both.
WATCHDOG_EXIT_CODE = 75


class TrainingDiverged(RuntimeError):
    """Raised by NonFiniteBreaker: too many consecutive non-finite-grad
    steps — the run is not glitching, it is diverging."""


class CheckpointUnrecoverable(IOError):
    """A checkpoint save exhausted its retry budget."""


class RetryPolicy:
    """Bounded exponential backoff with jitter for checkpoint IO.

    ``retries`` is the number of RE-tries after the first attempt (so
    ``retries=3`` means at most 4 attempts).  Backoff for attempt k is
    ``min(backoff_s * 2**k, max_backoff_s) * (1 + jitter * u)`` with
    ``u ~ U[0, 1)`` — the jitter decorrelates retry storms when many
    hosts hit the same flaky filesystem at once.
    """

    def __init__(
        self,
        retries: int = 3,
        *,
        backoff_s: float = 0.5,
        max_backoff_s: float = 8.0,
        jitter: float = 0.25,
        seed: int | None = None,
    ):
        if retries < 0:
            raise ValueError(f"retries must be >= 0, got {retries}")
        self.retries = retries
        self.backoff_s = backoff_s
        self.max_backoff_s = max_backoff_s
        self.jitter = jitter
        self._rng = random.Random(seed)

    def sleep(self, attempt: int) -> float:
        t = min(self.backoff_s * (2 ** attempt), self.max_backoff_s)
        t *= 1.0 + self.jitter * self._rng.random()
        time.sleep(t)
        return t


class ResilientCheckpointer(Checkpointer):
    """Checkpointer whose IO survives transient failure and corruption.

    Saves are synchronous-by-contract here: each ``save`` drives the
    async orbax write to completion and verifies the step was atomically
    finalized before returning, because a save that is still in flight
    when the worker is preempted is exactly the partial checkpoint this
    class exists to tolerate.  The verified-durable cost is paid at
    epoch cadence, off the step hot path.

    ``injector`` (a ``utils.chaos.FaultInjector``) is consulted inside
    the retry scope so chaos runs exercise the REAL retry/backoff path,
    not a parallel test-only one.  ``counters`` (``utils.metrics.
    FaultCounters``) makes retries/fallbacks visible in run metrics.
    """

    def __init__(
        self,
        directory: str,
        *,
        max_to_keep: int = 3,
        policy: RetryPolicy | None = None,
        injector=None,
        counters=None,
        events=None,
    ):
        super().__init__(directory, max_to_keep=max_to_keep)
        self._max_to_keep = max_to_keep
        self._policy = policy or RetryPolicy()
        self._injector = injector
        self._counters = counters
        # Optional observability EventLog: retries, fallbacks, and
        # committed saves land in the per-worker event stream alongside
        # the chaos injections that caused them.
        self._events = events
        self._saves = 0

    # -- save: bounded retry + verification ----------------------------
    def save(
        self, state: Pytree, epoch: int, *, meta: dict | None = None
    ) -> None:
        ordinal = self._saves
        self._saves += 1
        last_err: Exception | None = None
        for attempt in range(self._policy.retries + 1):
            try:
                if self._injector is not None:
                    self._injector.fail_io(ordinal, attempt)
                super().save(state, epoch, meta=meta)
                # Drive the async write to completion INSIDE the retry
                # scope: orbax surfaces async IO errors at wait time.
                super().wait()
                self._verify_saved(epoch)
                if self._events is not None:
                    self._events.emit(
                        "ckpt_save", epoch=epoch, attempts=attempt + 1
                    )
                return
            # ddplint: allow[broad-except] — retrying IO boundary
            except Exception as e:  # noqa: BLE001 — retrying IO boundary
                last_err = e
                if attempt >= self._policy.retries:
                    break
                if self._counters is not None:
                    self._counters.io_retries += 1
                if self._events is not None:
                    self._events.emit(
                        "ckpt_retry",
                        epoch=epoch, attempt=attempt, error=str(e),
                    )
                # A failed async save can leave the manager poisoned
                # (pending tmp dirs, a dead background thread): rebuild
                # it; CheckpointManager init sweeps incomplete step dirs.
                self._rebuild_manager()
                slept = self._policy.sleep(attempt)
                warn_all(
                    "checkpoint save (epoch %d) attempt %d failed: %s — "
                    "retrying after %.2fs backoff", epoch, attempt, e, slept
                )
        raise CheckpointUnrecoverable(
            f"checkpoint save for epoch {epoch} failed after "
            f"{self._policy.retries + 1} attempts"
        ) from last_err

    def _verify_saved(self, epoch: int) -> None:
        """Atomic-write verification: orbax finalizes a step by renaming
        its tmp dir into place, so a step that is LISTED is a step that
        committed; additionally require its metadata to be readable so a
        commit whose metadata write was torn still counts as a failure
        here (and gets retried) rather than at restore time."""
        if epoch not in self._mgr.all_steps():
            raise CheckpointUnrecoverable(
                f"step {epoch} missing from the manager's finalized steps "
                "after save — the write did not commit atomically"
            )
        self._mgr.item_metadata(epoch)

    def _rebuild_manager(self) -> None:
        import orbax.checkpoint as ocp

        try:
            self._mgr.close()
        # ddplint: allow[broad-except] — closing an already-broken manager
        except Exception:  # noqa: BLE001 — already-broken manager
            pass
        self._mgr = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=self._max_to_keep,
                enable_async_checkpointing=True,
            ),
        )

    # -- restore: corrupt-checkpoint fallback --------------------------
    def restore_latest(
        self, state: Pytree, *, template: Pytree | None = None
    ) -> tuple[Pytree, int]:
        """Like ``Checkpointer.restore_latest``, but a step that fails to
        restore (truncated array file, torn metadata, structure garbage)
        is quarantined — renamed out of orbax's view, kept on disk for
        post-mortem — and the NEXT newest step is tried, down to a fresh
        start when nothing intact remains."""
        while True:
            step = self._mgr.latest_step()
            if step is None:
                return state, 0
            try:
                return super().restore_latest(state, template=template)
            # ddplint: allow[broad-except] — corrupt-ckpt fault boundary
            except Exception as e:  # noqa: BLE001 — fault boundary
                if self._counters is not None:
                    self._counters.ckpt_fallbacks += 1
                if self._events is not None:
                    self._events.emit(
                        "ckpt_fallback", step=step, error=str(e)
                    )
                warn_all(
                    "checkpoint step %d is corrupt or unreadable (%s: %s) "
                    "— quarantining it and falling back to the previous "
                    "step", step, type(e).__name__, e
                )
                self._quarantine(step)

    def _quarantine(self, step: int) -> None:
        """Move the bad step directory aside (``<name>.corrupt``) so the
        manager no longer sees it; deletion would destroy the evidence."""
        path = self._step_dir(step)
        if path is not None:
            dst = path + ".corrupt"
            if os.path.exists(dst):  # quarantined twice: make it unique
                dst = f"{dst}.{int(time.time() * 1e3)}"
            os.replace(path, dst)
        self._rebuild_manager()
        if self._mgr.latest_step() == step:
            # Refuse to loop forever on a step we cannot even move aside.
            raise CheckpointUnrecoverable(
                f"could not quarantine corrupt checkpoint step {step} "
                f"under {self._dir}"
            )

    def _step_dir(self, step: int) -> str | None:
        """The step's directory under the manager root, tolerating the
        common orbax name formats (``8``, ``step_8``, zero-padded)."""
        for name in sorted(os.listdir(self._dir)):
            full = os.path.join(self._dir, name)
            if not os.path.isdir(full):
                continue
            tail = name.rsplit("_", 1)[-1]
            try:
                if int(tail) == step:
                    return full
            except ValueError:
                continue
        return None


class StepWatchdog:
    """Wall-clock deadline on train-loop heartbeats.

    The failure mode this guards against is the worst one a pod run has:
    a wedged collective (one host preempted mid all-reduce) hangs the
    step forever with no exception to catch.  The loop calls ``beat()``
    once per iteration; dispatch is async, so a wedged device shows up
    as the loop stalling at its next sync point (metrics read, timer
    window, checkpoint) — the heartbeats stop, and after ``timeout_s``
    the watchdog fires from its monitor thread:

    1. logs a diagnostic with the last-known loop state (the kwargs of
       the final ``beat``), seconds since that beat, and the device
       roster captured at ``start()`` (captured early — querying a
       wedged runtime from the watchdog thread could itself hang);
    2. runs ``on_timeout(diagnostic)`` — the CLI wires a best-effort
       checkpoint of the last COMPLETED state here;
    3. force-exits with ``exit_code`` (default 75) so supervision
       restarts the worker — a ``grace_s`` timer guarantees the exit
       even if the checkpoint attempt itself wedges.

    ``exit_process=False`` (tests, library embedding) skips step 3 and
    instead records the diagnostic in ``self.fired``.

    Arm it AFTER the first completed step: the first step carries
    compilation (minutes for big models) and would need a meaninglessly
    long deadline.
    """

    def __init__(
        self,
        timeout_s: float,
        *,
        on_timeout: Callable[[dict], None] | None = None,
        exit_process: bool = True,
        exit_code: int = WATCHDOG_EXIT_CODE,
        grace_s: float = 30.0,
        poll_s: float | None = None,
    ):
        if timeout_s <= 0:
            raise ValueError(f"timeout_s must be > 0, got {timeout_s}")
        self.timeout_s = timeout_s
        self.on_timeout = on_timeout
        self.exit_process = exit_process
        self.exit_code = exit_code
        self.grace_s = grace_s
        self._poll_s = poll_s if poll_s is not None else min(
            timeout_s / 4.0, 1.0
        )
        self.fired: dict | None = None
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._last_beat: float | None = None
        self._context: dict = {}
        self._devices: list[str] = []

    def start(self, **context) -> "StepWatchdog":
        if self._thread is not None:
            return self
        try:
            self._devices = [str(d) for d in jax.devices()]
        # ddplint: allow[broad-except] — diagnostics only
        except Exception:  # noqa: BLE001 — diagnostics only
            self._devices = ["<device query failed>"]
        with self._lock:
            self._last_beat = time.monotonic()
            self._context = dict(context)
        self._thread = threading.Thread(
            target=self._run, name="step-watchdog", daemon=True
        )
        self._thread.start()
        return self

    @property
    def running(self) -> bool:
        return self._thread is not None

    def beat(self, **context) -> None:
        """Heartbeat: the loop is alive.  ``context`` kwargs (epoch,
        batch, step...) become the diagnostic's last-known state."""
        with self._lock:
            self._last_beat = time.monotonic()
            if context:
                self._context = dict(context)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def __enter__(self) -> "StepWatchdog":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def _run(self) -> None:
        while not self._stop.wait(self._poll_s):
            with self._lock:
                last = self._last_beat
                ctx = dict(self._context)
            if last is None:
                continue
            stalled = time.monotonic() - last
            if stalled > self.timeout_s:
                self._fire(stalled, ctx)
                return

    def _fire(self, stalled_s: float, ctx: dict) -> None:
        diag = {
            "seconds_since_heartbeat": round(stalled_s, 3),
            "timeout_s": self.timeout_s,
            "last_known_state": ctx,
            "devices": self._devices,
        }
        self.fired = diag
        warn_all(
            "step watchdog: no heartbeat for %.1fs (deadline %.1fs) — "
            "last-known state %s on devices %s; forcing "
            "checkpoint-then-exit rather than hanging",
            stalled_s, self.timeout_s, ctx, self._devices,
        )
        if self.exit_process:
            # The exit must not depend on the checkpoint attempt
            # cooperating: a wedged runtime can hang a save forever.
            killer = threading.Timer(
                self.grace_s, os._exit, args=(self.exit_code,)
            )
            killer.daemon = True
            killer.start()
        try:
            if self.on_timeout is not None:
                self.on_timeout(diag)
        finally:
            if self.exit_process:
                os._exit(self.exit_code)


class NonFiniteBreaker:
    """Consecutive-bad-step circuit breaker for the non-finite-grad guard.

    The compiled step (``make_train_step(nonfinite_guard=True)``) skips
    a bad step's update and reports ``metrics['nonfinite_grad']``; this
    host-side breaker turns a RUN of them into a hard stop — an isolated
    overflow is weather, N in a row is divergence, and silently skipping
    forever would burn a pod on a run that is already dead.
    """

    def __init__(self, max_consecutive: int = 5):
        if max_consecutive < 1:
            raise ValueError(
                f"max_consecutive must be >= 1, got {max_consecutive}"
            )
        self.max_consecutive = max_consecutive
        self.consecutive = 0
        self.total = 0

    def observe(self, nonfinite) -> int:
        """Feed one step's ``metrics['nonfinite_grad']`` (0/1; anything
        float-able).  Returns the current consecutive count; raises
        TrainingDiverged at the threshold."""
        if float(nonfinite) > 0:
            self.consecutive += 1
            self.total += 1
            if self.consecutive >= self.max_consecutive:
                raise TrainingDiverged(
                    f"{self.consecutive} consecutive non-finite-gradient "
                    f"steps (threshold {self.max_consecutive}): the run is "
                    "diverging — lower the LR / raise warmup / check the "
                    "data pipeline, then resume from the last checkpoint"
                )
        else:
            self.consecutive = 0
        return self.consecutive


def note_warm_start(
    counters, *, mode: str, first_step_s: float | None = None, events=None,
    cache_hits: int | None = None, cache_misses: int | None = None,
) -> None:
    """Record how this incarnation obtained its train step.

    Called once per process start (including every supervised respawn —
    ``DDP_RESTART_ATTEMPT`` carries the attempt index) so the restart
    path's warm-start behavior is visible in the normal run log and in
    the fault summary: a respawn that was supposed to hit the cache but
    logs ``cold`` is a warm-start regression, caught by reading logs
    instead of by profiling.  ``cache_hits`` / ``cache_misses`` count
    JAX persistent-compilation-cache lookups made while the first step
    was acquired: a warm start of a plain ``jit`` step is hits > 0 with
    no miss.
    """
    from distributeddataparallel_tpu.utils.logging import log0

    counters.warm_start_mode = mode
    if first_step_s is not None:
        counters.compile_s = first_step_s
    attempt = int(os.environ.get("DDP_RESTART_ATTEMPT", "0") or 0)
    if events is not None:
        events.emit(
            "warm_start",
            mode=mode, first_step_s=first_step_s, attempt=attempt,
            cache_hits=cache_hits, cache_misses=cache_misses,
        )
    log0(
        "warm start: attempt %d acquired the train step via %s%s%s",
        attempt, mode,
        f" (first step ready in {first_step_s:.2f}s)"
        if first_step_s is not None else "",
        f", compile cache {cache_hits} hit(s) / {cache_misses} miss(es)"
        if cache_hits is not None else "",
    )
