"""Launcher: the TPU-native analog of ``torch.multiprocessing.spawn``.

The reference fans out one OS process per GPU with
``mp.spawn(train, args=(world_size,), nprocs=world_size, join=True)``
(ref dpp.py:62).  On TPU the idiomatic topology is one process per *host*,
with all local chips driven through the mesh by a single jit'd SPMD program —
so on a single host, "spawn" is simply a function call, and across hosts the
fan-out is done by the cluster scheduler (one command per TPU VM), not by
forking.

``spawn`` therefore:

- runs ``fn(process_id, *args)`` in-process for the common one-host case
  (covering every local chip via the mesh — the work the reference needed
  ``world_size`` processes for happens inside one XLA program);
- when ``nprocs > 1`` is requested explicitly (CPU simulation of a
  multi-host job), forks real OS processes, each with its own
  ``jax.distributed`` rendezvous over a localhost coordinator — the moral
  equivalent of the reference's TCPStore env:// rendezvous, but
  self-contained (no MASTER_ADDR/MASTER_PORT to export; SURVEY.md §2d.1);
- with ``max_restarts > 0``, SUPERVISES: the worker gang always runs in
  child processes (nprocs=1 included — the supervisor must survive the
  worker's death), and any non-zero exit respawns the whole gang, up to
  the budget.  Paired with checkpoint/elastic-resume in the worker, this
  is the torchrun ``--max-restarts`` analog — the piece that turns a
  preemption from a lost run into a resumed one.

``join=True`` semantics from the reference (block, propagate child failure)
are preserved.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import socket
import time
from typing import Any, Callable, Sequence


MULTIPROCESS_UNSUPPORTED_EXIT = 86


def guarded_worker(fn, process_id, *args):
    """Run a gang worker, converting a backend capability gap into the
    sentinel ``MULTIPROCESS_UNSUPPORTED_EXIT``: some PJRT clients (this
    jaxlib's CPU backend among them) refuse any computation that spans
    processes, and a supervisor or test harness wants to tell "this
    environment cannot do multiprocess at all" apart from a real crash.
    Wrap a worker with ``functools.partial(guarded_worker, fn)`` — the
    partial of a module-level function survives the spawn pickling.
    """
    try:
        fn(process_id, *args)
    # ddplint: allow[broad-except] — re-raises; only maps one message to a
    # sentinel exit code
    except Exception as exc:
        if "Multiprocess computations aren't implemented" in str(exc):
            raise SystemExit(MULTIPROCESS_UNSUPPORTED_EXIT) from exc
        raise


def _free_port() -> int:
    # ddplint: allow[blocking-socket] — local loopback bind to probe a
    # free port; there is no remote peer whose absence a retry could fix
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child(fn, process_id, nprocs, coordinator, env, args):
    # Runs in a fresh interpreter (spawn start method).  Importing this
    # module imported jax, so ``env`` is too late for what jax reads at
    # import (JAX_PLATFORMS, JAX_COMPILATION_CACHE_DIR: those a child
    # inherits from the parent's environment); it is in time for what
    # the worker and backend initialization read.
    os.environ.update(env)
    if nprocs > 1:
        # A single supervised worker must NOT get distributed-init vars:
        # it is a one-process job that happens to run in a child, and a
        # stale JAX_COORDINATOR_ADDRESS would make it block on rendezvous.
        os.environ["JAX_COORDINATOR_ADDRESS"] = coordinator
        os.environ["JAX_NUM_PROCESSES"] = str(nprocs)
        os.environ["JAX_PROCESS_ID"] = str(process_id)
    fn(process_id, *args)


def _run_gang(fn, args, nprocs, env) -> list:
    """Fork one gang (fresh coordinator port per gang: a restarted gang
    must not race the dead one's lingering socket)."""
    coordinator = f"127.0.0.1:{_free_port()}"
    ctx = mp.get_context("spawn")
    procs = []
    for i in range(nprocs):
        p = ctx.Process(
            target=_child,
            args=(fn, i, nprocs, coordinator, dict(env or {}), tuple(args)),
            daemon=False,
        )
        p.start()
        procs.append(p)
    return procs


def _join_gang(procs) -> list[tuple[int, int]]:
    """Join every member; returns [(rank, exitcode)] for the failed ones."""
    failed = []
    for i, p in enumerate(procs):
        p.join()
        if p.exitcode != 0:
            failed.append((i, p.exitcode))
    return failed


def _last_fault(elastic_store: str | None) -> dict | None:
    """Most recent chaos breadcrumb from the shared fault log (written
    by ``FaultInjector`` when ``fault_log`` / ``DDP_FAULT_LOG`` is
    wired), or None — the attribution a ``gang_verdict`` carries so the
    verdict names the fault that triggered the ladder."""
    if not elastic_store:
        return None
    import json

    last = None
    try:
        with open(os.path.join(elastic_store, "faults.jsonl")) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict):
                    last = rec
    except OSError:
        return None
    return last


def _absorbed_resize(elastic_store: str, failed, min_procs: int) -> bool:
    """Did the surviving gang already absorb the failed ranks IN PLACE
    (the multi-host in-place resize: survivors ran the epoch transition
    and finished while the dead rank's exit is the only non-zero code)?

    True iff every failed launcher rank published a member binding
    (``rank:<i>`` blob, written by hostgang members at join), every such
    member is tombstoned AND out of the agreed roster, and the roster
    still meets the ``min_procs`` floor.  A rank with no binding (the
    one-process CPU-sim gang) or an untombstoned member (an organic
    crash nobody shed) is NOT absorbed — those take the respawn rungs.
    """
    from distributeddataparallel_tpu.runtime.rendezvous import (
        RendezvousStore,
    )

    try:
        store = RendezvousStore(elastic_store)
        names = []
        for rank, _code in failed:
            blob = store.get_blob(f"rank:{rank}")
            if not blob:
                return False
            names.append(blob.strip())
        cur = store.epoch()
        if cur["epoch"] < 0:
            return False
        roster = set(cur["roster"])
        dead = set(store.dead())
        if any(n in roster or n not in dead for n in names):
            return False
        return len(roster) >= max(min_procs, 1)
    except (OSError, RuntimeError, ValueError):
        return False  # torn/unreadable store: not absorbed, ladder on


def _elastic_survivors(elastic_store: str):
    """Roster state from an elastic rendezvous store: ``(store, epoch,
    roster, survivors)``, or None when the store has no epoch yet.

    Survivorship is decided by TOMBSTONES only (``mark_dead`` /
    ``leave``), never by heartbeat freshness: when a supervised gang dies
    seconds ago, every member's heartbeat file still looks fresh — the
    tombstone a chaos kill (or a peer's failure detector) wrote is the
    one signal that distinguishes "this member was removed from the
    gang" from "the whole process just went down".  Import-light: the
    rendezvous store is stdlib-only, safe in the supervisor.
    """
    from distributeddataparallel_tpu.runtime.rendezvous import (
        RendezvousStore,
    )

    store = RendezvousStore(elastic_store)
    cur = store.epoch()
    if cur["epoch"] < 0:
        return None
    dead = store.dead()
    roster = list(cur["roster"])
    survivors = [m for m in roster if m not in dead]
    return store, cur["epoch"], roster, survivors


def spawn(
    fn: Callable[..., Any],
    args: Sequence[Any] = (),
    nprocs: int = 1,
    join: bool = True,
    *,
    env: dict[str, str] | None = None,
    max_restarts: int = 0,
    restart_backoff_s: float = 1.0,
    events_dir: str | None = None,
    runs_dir: str | None = None,
    elastic_store: str | None = None,
    min_procs: int = 1,
):
    """Run ``fn(i, *args)`` for i in range(nprocs).

    nprocs=1 (the TPU-native default): direct call, no fork — one process
    drives all local chips. nprocs>1: real OS processes with a localhost
    coordinator, used to exercise the true multi-process code path on CPU.

    ``max_restarts > 0`` adds supervision (torchrun ``--max-restarts``
    semantics): the gang runs in child processes even for nprocs=1, and
    when ANY member exits non-zero — a crash, a preemption kill, the step
    watchdog's deliberate exit-75 — the WHOLE gang is respawned (after
    joining the survivors; a partial gang cannot rendezvous) with a fresh
    coordinator port, up to ``max_restarts`` times with linear backoff.
    The worker owns resume correctness: it must restore from its latest
    checkpoint on startup (``--resume`` / elastic restore), which is what
    makes restart-from-zero into restart-from-last-epoch.  Requires
    ``join=True`` — supervision IS a blocking join loop.

    ``events_dir`` enables supervisor-side observability: restart
    attempts are recorded in ``events-supervisor.jsonl`` (the supervisor
    is the only process that SEES a gang die, so only it can log the
    respawn), workers inherit the directory via ``DDP_EVENTS_DIR``, and
    on exit every per-writer file is merged into one gang
    ``timeline.jsonl`` ordered by (ts, seq).

    ``runs_dir`` (with ``events_dir``) additionally appends a
    run_summary extracted from the merged timeline to the longitudinal
    runs store (``observability.baseline``) — the supervisor writes it
    because only its view spans every incarnation plus the restart gaps
    between them.  Workers inherit the directory via ``DDP_RUNS_DIR``.

    ``elastic_store`` (a ``runtime.rendezvous`` root, with supervision)
    switches the death path from restart to RESIZE when the gang's
    membership shrank: if the store's tombstones show the dead gang had
    already lost members (a chaos worker-kill, a peer failure detector),
    the supervisor respawns at the surviving size via
    ``DDP_ELASTIC_WORLD`` — consuming NO restart budget and emitting
    ``gang_resize``/``resize_downtime`` instead of ``restart_attempt``.
    A death with an intact roster still takes the normal restart path.
    ``min_procs`` floors the resize: fewer survivors than that is a
    failure, not a smaller gang.
    """
    if nprocs < 1:
        raise ValueError(f"nprocs must be >= 1, got {nprocs}")
    if max_restarts < 0:
        raise ValueError(f"max_restarts must be >= 0, got {max_restarts}")
    if max_restarts > 0:
        if not join:
            raise ValueError(
                "max_restarts needs join=True: supervision is a blocking "
                "join-and-respawn loop, there is no handle to return"
            )
        from distributeddataparallel_tpu.utils.logging import get_logger

        sup_events = None
        if events_dir:
            from distributeddataparallel_tpu.observability.events import (
                EventLog,
            )

            sup_events = EventLog(
                os.path.join(events_dir, "events-supervisor.jsonl"),
                "supervisor",
            )
        def _verdict(rung: str, **detail) -> None:
            """The degradation ladder's terminal record: which rung this
            run ended on (resize / restart / fail), attributed to the
            chaos fault that triggered it (None for organic failures).
            Emitted once, at the supervisor — the only process whose view
            spans every incarnation."""
            if sup_events is None:
                return
            fault = _last_fault(elastic_store)
            sup_events.emit(
                "gang_verdict",
                rung=rung,
                fault=None if fault is None else fault.get("entry"),
                fault_kind=None if fault is None else fault.get("kind"),
                **detail,
            )

        def _resized_in_place() -> bool:
            """Did the gang itself run at least one epoch transition
            beyond the initial roster (in-place resize, no respawn)?"""
            if elastic_store is None:
                return False
            from distributeddataparallel_tpu.runtime.rendezvous import (
                RendezvousStore,
            )

            try:
                return len(RendezvousStore(elastic_store).history()) > 1
            except OSError:
                return False

        try:
            attempt = 0
            resizes = 0
            world_override: int | None = None
            while True:
                # The worker can surface its incarnation
                # (FaultCounters.restarts, log lines) without any side
                # channel back from the supervisor.
                gang_env = dict(env or {})
                gang_env["DDP_RESTART_ATTEMPT"] = str(attempt)
                if world_override is not None:
                    gang_env["DDP_ELASTIC_WORLD"] = str(world_override)
                if events_dir:
                    gang_env.setdefault("DDP_EVENTS_DIR", events_dir)
                if runs_dir:
                    gang_env.setdefault("DDP_RUNS_DIR", runs_dir)
                procs = _run_gang(fn, args, nprocs, gang_env)
                failed = _join_gang(procs)
                if not failed:
                    # Clean finish: name the rung the run used to get
                    # here.  restart dominates resize in the verdict
                    # (budget was consumed); a fault absorbed without
                    # either respawn is the in-place resize rung (an
                    # epoch transition, or a store re-host / recovered
                    # suspect that never changed membership).
                    fault = _last_fault(elastic_store)
                    if attempt > 0:
                        _verdict("restart", attempts=attempt)
                    elif resizes > 0 or _resized_in_place():
                        _verdict("resize", respawns=resizes)
                    elif fault is not None:
                        _verdict("resize", respawns=0)
                    return None
                t_died = time.perf_counter()
                if (
                    elastic_store is not None
                    and _absorbed_resize(elastic_store, failed, min_procs)
                ):
                    # Multi-host in-place resize: the dead rank's exit is
                    # the only failure, the survivors tombstoned it, ran
                    # the epoch transition, and finished their run — the
                    # gang already took the first ladder rung, nothing to
                    # respawn.
                    _verdict("resize", respawns=resizes, failed=failed)
                    get_logger().warning(
                        "[supervisor] rank(s) %s died but the surviving "
                        "gang absorbed the loss in place (elastic resize) "
                        "— run complete, no respawn",
                        [r for r, _ in failed],
                    )
                    return None
                info = None
                if elastic_store is not None:
                    try:
                        info = _elastic_survivors(elastic_store)
                    except RuntimeError:
                        # Torn epoch store beyond self-heal: membership
                        # is unreadable, so a resize is off the table —
                        # fall through to the checkpoint-restart rung.
                        info = None
                if info is not None:
                    store, epoch, roster, survivors = info
                    if (
                        set(survivors) != set(roster)
                        and len(survivors) >= max(min_procs, 1)
                    ):
                        # Resize, not restart: the gang lost members
                        # before it died, so respawn at the surviving
                        # size.  Tombstone the WHOLE old roster first —
                        # the process is dead, so every heartbeat in the
                        # store is a ghost; the respawned coordinator
                        # re-joins its members (clearing their
                        # tombstones) and proposes the next epoch over
                        # exactly the members that actually came back.
                        world_override = len(survivors)
                        resizes += 1
                        for m in roster:
                            store.leave(m)
                        if sup_events is not None:
                            sup_events.emit(
                                "gang_resize",
                                epoch=epoch + 1,
                                old_size=len(roster),
                                new_size=len(survivors),
                                left=sorted(set(roster) - set(survivors)),
                            )
                            sup_events.emit(
                                "resize_downtime",
                                epoch=epoch + 1,
                                seconds=round(
                                    time.perf_counter() - t_died, 3
                                ),
                            )
                        get_logger().warning(
                            "[supervisor] gang died with a shrunk roster "
                            "(%d -> %d members) — elastic resize-respawn, "
                            "restart budget untouched (%d/%d used)",
                            len(roster), len(survivors),
                            attempt, max_restarts,
                        )
                        continue
                if attempt >= max_restarts:
                    if sup_events is not None:
                        sup_events.emit(
                            "restart_exhausted",
                            attempt=attempt, failed=failed,
                            max_restarts=max_restarts,
                        )
                    # The ladder's last rung: resize was impossible (or
                    # already tried), the restart budget is gone — fail
                    # LOUDLY, with the triggering fault named.
                    _verdict(
                        "fail", attempts=attempt, failed=failed,
                        max_restarts=max_restarts,
                    )
                    raise RuntimeError(
                        f"spawned processes failed (rank, exitcode): {failed} "
                        f"— restart budget of {max_restarts} exhausted"
                    )
                if sup_events is not None:
                    sup_events.emit(
                        "restart_attempt",
                        attempt=attempt + 1, failed=failed,
                        max_restarts=max_restarts,
                    )
                get_logger().warning(
                    "[supervisor] gang failed (rank, exitcode): %s — "
                    "restart %d/%d after %.1fs",
                    failed, attempt + 1, max_restarts,
                    restart_backoff_s * (attempt + 1),
                )
                time.sleep(restart_backoff_s * (attempt + 1))
                attempt += 1
        finally:
            if sup_events is not None:
                sup_events.close()
            if events_dir:
                from distributeddataparallel_tpu.observability.events import (
                    merge_timeline,
                )

                # Best-effort: the merge runs while a restart-exhausted
                # RuntimeError may be propagating, and a merge failure
                # (unwritable dir, disk full, a gang that died before
                # any worker wrote its file) must not mask it.
                try:
                    merged = merge_timeline(events_dir)
                    if merged is None:
                        get_logger().warning(
                            "[supervisor] no event files to merge in %s "
                            "(gang died before writing any?)",
                            events_dir,
                        )
                    elif runs_dir:
                        # Longitudinal store: the supervisor's summary is
                        # THE record for a supervised run — rebuilt from
                        # the merged timeline, it spans every incarnation
                        # and the restart gaps no worker could see.
                        # Best-effort for the same reason as the merge.
                        from distributeddataparallel_tpu.observability import (
                            baseline as _baseline,
                        )
                        from distributeddataparallel_tpu.observability.events import (  # noqa: E501
                            load_timeline,
                        )

                        _baseline.append_run(
                            runs_dir,
                            _baseline.run_summary_from_timeline(
                                load_timeline(events_dir)
                            ),
                            source="supervisor",
                        )
                except OSError as exc:
                    get_logger().warning(
                        "[supervisor] timeline merge failed in %s: %s",
                        events_dir, exc,
                    )

    if nprocs == 1:
        fn(0, *args)
        return None

    procs = _run_gang(fn, args, nprocs, env)
    if not join:
        return procs
    failed = _join_gang(procs)
    if failed:
        # Mirror mp.spawn join=True: surface child failure in the parent.
        raise RuntimeError(f"spawned processes failed (rank, exitcode): {failed}")
    return None
