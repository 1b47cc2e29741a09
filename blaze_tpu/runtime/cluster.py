"""Driver-side worker pool: OS-process executors for shuffle map stages.

Reference: Spark schedules map tasks onto executor JVMs and retries failed
or lost tasks (``AuronShuffleManager`` + Spark's TaskScheduler, SURVEY.md
§3.3/§5.3). Standalone equivalents here:

- ``WorkerPool`` spawns ``python -m blaze_tpu.runtime.worker`` subprocesses
  that dial back over a unix socket;
- tasks ship as protobuf ``TaskDefinition`` bytes (the SAME wire contract a
  JVM frontend would use — the proto seam is exercised across a real
  process boundary);
- a worker dying mid-task (socket EOF) or erroring marks the task for
  retry on another worker, up to ``max_task_retries``; dead workers are
  respawned to keep the fleet size.

Worker supervision (the executor-liveness story Spark's driver heartbeats
provide): a supervisor thread probes every worker process each
``fault_heartbeat_interval_s`` so deaths are noticed between stages, not
only when a mid-task recv fails. Every death is counted
(``blaze_cluster_worker_deaths_total``), written as a flight-recorder
incident bundle (kind ``worker_lost``, served at ``/debug/incidents``),
and puts the worker slot on a TTL'd exclusion list
(``fault_exclusion_ttl_s``) — its respawned process (exponential backoff,
``fault_respawn_backoff_s``) sits out new task pulls while any other
worker is eligible. More than ``fault_max_worker_deaths`` deaths within a
single stage trips a circuit breaker: the stage aborts with the typed
``WorkerPoolBroken`` instead of retrying forever (the serve layer maps it
to a retryable error).
"""

from __future__ import annotations

import os
import queue
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

import logging

from blaze_tpu.obs.telemetry import get_registry
from blaze_tpu.runtime.ipc import recv_msg, send_msg

log = logging.getLogger("blaze_tpu.cluster")

_TM_WORKER_DEATHS = get_registry().counter(
    "blaze_cluster_worker_deaths_total",
    "worker processes observed dead (killed, crashed, or OOMed)")
_TM_TASKS_RETRIED = get_registry().counter(
    "blaze_cluster_tasks_retried_total",
    "pool tasks re-queued after a failure or worker loss")
_TM_TASKS_TIMED_OUT = get_registry().counter(
    "blaze_cluster_tasks_timed_out_total",
    "in-flight task attempts hard-cancelled after exceeding task_timeout_s")


class TaskFailed(RuntimeError):
    pass


class WorkerPoolBroken(TaskFailed):
    """Circuit breaker: too many worker deaths within one stage. Typed so
    the serving layer can classify the failure as retryable infrastructure
    loss rather than a query bug."""


class _Worker:
    def __init__(self, pool: "WorkerPool", wid: int):
        self.pool = pool
        self.wid = wid
        self.proc: Optional[subprocess.Popen] = None
        self.sock: Optional[socket.socket] = None
        self.in_flight = False
        # (task, attempt, started_at) while a send/recv is outstanding —
        # the hard-timeout monitor reads it to find hung attempts
        self.current_task: Optional[tuple] = None
        # attempts this PROCESS has answered (reset on every spawn): the
        # hard-timeout monitor grants the first task of a fresh process a
        # cold-start grace multiple of task_timeout_s, because it carries
        # JIT compile/setup cost a steady-state bound would misread as a
        # hang — killing every fresh respawn in a cascade
        self.tasks_done_gen = 0
        # death bookkeeping: ``generation`` bumps on every (re)spawn and
        # ``dead_gen`` records the last generation whose death was noted —
        # the pair dedups the supervisor and the serve thread both
        # observing the same corpse (and suppresses deliberate driver-side
        # resets, which pre-mark dead_gen)
        self.generation = 0
        self.dead_gen = -1

    def spawn(self):
        env = dict(os.environ)
        # the driver holds the chip; its children never ask for it
        env["JAX_PLATFORMS"] = "cpu"
        # slot-stable failpoint stream salt (runtime/failpoints._salt):
        # symmetric workers must not draw identical injection streams
        env["BLAZE_TPU_FAILPOINT_SALT"] = str(self.wid + 1)
        overall = time.monotonic() + 120.0
        while True:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "blaze_tpu.runtime.worker",
                 self.pool.sock_path],
                env=env, cwd=self.pool.repo_root)
            sock = self._accept_hello()
            if sock is not None:
                self.sock = sock
                self.tasks_done_gen = 0
                return
            # the fresh process died before completing its hello (crashed
            # on import, OOM-killed, or chaos-killed mid-spawn): reap and
            # retry. A blocking accept here would wedge _spawn_mu — and
            # with it every serve thread of the next stage — forever.
            log.warning("worker %d died during spawn (exit=%s); retrying",
                        self.wid, self.proc.poll())
            self.kill()
            if time.monotonic() >= overall:
                raise RuntimeError(
                    f"worker {self.wid}: spawn kept dying for 120s")

    def _accept_hello(self) -> Optional[socket.socket]:
        """Accept the fresh process's connection + hello, bounded: returns
        None (instead of blocking forever) when the process dies first."""
        listener = self.pool.listener
        listener.settimeout(0.5)
        try:
            deadline = time.monotonic() + 60.0  # worker import ~2-4s warm
            while True:
                try:
                    sock, _ = listener.accept()
                    break
                except socket.timeout:
                    if self.proc.poll() is not None \
                            or time.monotonic() >= deadline:
                        return None
            sock.settimeout(30.0)
            try:
                hello = recv_msg(sock)
            except (EOFError, OSError):  # includes socket.timeout
                sock.close()
                return None
            sock.settimeout(None)
            log.info("worker %d up (pid %s)", self.wid, hello.get("hello"))
            return sock
        finally:
            listener.settimeout(None)

    def kill(self):
        try:
            if self.sock is not None:
                self.sock.close()
        except OSError:
            pass
        # sock=None marks the channel dead even while the OS hasn't reaped
        # the process yet (poll() can lag a self-exit) — _respawn keys its
        # already-alive short-circuit on BOTH proc and sock
        self.sock = None
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=10)


_SPECULATIVE = -1  # attempt marker: failures of a speculative copy are ignored


class WorkerPool:
    def __init__(self, num_workers: int, max_task_retries: int = 2,
                 speculation_min_s: float = 5.0, conf=None):
        from blaze_tpu.config import get_config

        self.conf = conf or get_config()
        self.num_workers = num_workers
        self.max_task_retries = max_task_retries
        # a task must have been running this long before an idle worker may
        # launch its ONE speculative copy (Spark gates on a runtime quantile)
        self.speculation_min_s = speculation_min_s
        self.max_worker_deaths = self.conf.fault_max_worker_deaths
        self.exclusion_ttl_s = self.conf.fault_exclusion_ttl_s
        self.respawn_backoff_s = self.conf.fault_respawn_backoff_s
        self.heartbeat_interval_s = self.conf.fault_heartbeat_interval_s
        self.repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        self._sockdir = tempfile.mkdtemp(prefix="blaze_pool_")
        self.sock_path = os.path.join(self._sockdir, "driver.sock")
        self.listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self.listener.bind(self.sock_path)
        self.listener.listen(num_workers + 4)
        self.workers: List[_Worker] = []
        self._mu = threading.Lock()
        self._spawn_mu = threading.Lock()  # serializes listener.accept users
        # stages serialize on one lock: run_tasks owns every worker socket
        # for its duration, so two concurrent queries shipping stages (a
        # serving session over a pool) must take turns rather than
        # interleave frames on the same channels
        self._stage_mu = threading.Lock()
        self._stage_active = False
        self.deaths_total = 0
        self._death_counts: Dict[int, int] = {}  # wid -> lifetime deaths
        self._excluded: Dict[int, float] = {}  # wid -> excluded-until mono
        for i in range(num_workers):
            w = _Worker(self, i)
            w.spawn()
            self.workers.append(w)
        self._closed = threading.Event()
        self._supervisor = threading.Thread(
            target=self._supervise, name="pool-supervisor", daemon=True)
        self._supervisor.start()

    # -- supervision -----------------------------------------------------------

    def _supervise(self):
        """Liveness probe: notice worker deaths between recv calls. During
        a stage only the NOTING happens here (the serve thread owning the
        socket performs the respawn when its send/recv fails); between
        stages the supervisor also respawns, so the next stage starts with
        a full fleet instead of paying the spawn latency mid-stage."""
        while not self._closed.wait(self.heartbeat_interval_s):
            for w in list(self.workers):
                proc = w.proc
                if proc is None or proc.poll() is None:
                    continue
                self._note_death(w, "heartbeat")
                if not self._stage_active:
                    try:
                        self._respawn(w)
                    except Exception as exc:
                        log.error("supervisor respawn of worker %d failed: "
                                  "%s", w.wid, exc)

    def _note_death(self, w: _Worker, context: str,
                    task: Optional[int] = None) -> bool:
        """Record ONE death per worker generation: counters, exclusion,
        and a forensic incident bundle. Returns False when this generation's
        death was already noted (or was a deliberate driver reset)."""
        with self._mu:
            if w.dead_gen >= w.generation:
                return False
            w.dead_gen = w.generation
            self.deaths_total += 1
            self._death_counts[w.wid] = self._death_counts.get(w.wid, 0) + 1
            self._excluded[w.wid] = time.monotonic() + self.exclusion_ttl_s
            deaths = self._death_counts[w.wid]
            pid = w.proc.pid if w.proc is not None else None
            code = w.proc.poll() if w.proc is not None else None
        _TM_WORKER_DEATHS.inc()
        log.warning("worker %d (pid %s) died [%s] exit=%s; excluded for "
                    "%.0fs (death %d of this slot, %d pool-wide)",
                    w.wid, pid, context, code, self.exclusion_ttl_s,
                    deaths, self.deaths_total)
        try:
            from blaze_tpu.obs.dump import record_incident

            record_incident(
                "worker_lost", f"worker_{w.wid}", conf=self.conf,
                extra={"wid": w.wid, "pid": pid, "exit_code": code,
                       "context": context, "task": task,
                       "generation": w.generation,
                       "slot_deaths": deaths,
                       "pool_deaths_total": self.deaths_total,
                       "in_flight": w.in_flight})
        except Exception:
            log.warning("incident bundle for worker %d failed", w.wid,
                        exc_info=True)
        return True

    def _respawn(self, w: _Worker, abort: Optional[threading.Event] = None):
        """Replace a dead worker process, with exponential backoff keyed on
        the slot's lifetime death count (a crash-looping slot slows down
        instead of thrashing spawn). ``abort`` (the stage's done event)
        cancels a respawn still waiting out its backoff: a stage that
        finished meanwhile leaves the slot to the supervisor instead of
        stalling its own end behind the sleep + spawn."""
        with self._spawn_mu:
            if w.proc is not None and w.proc.poll() is None \
                    and w.sock is not None:
                return  # already alive (lost a race with another respawner)
            with self._mu:
                n = self._death_counts.get(w.wid, 1)
            delay = min(self.respawn_backoff_s * (2 ** max(0, n - 1)), 10.0)
            if abort is not None:
                if abort.wait(delay):
                    return  # stage over; leave the corpse to the supervisor
            elif delay > 0:
                time.sleep(delay)
            w.kill()
            w.spawn()
            with self._mu:
                w.generation += 1

    def _reset_worker(self, w: _Worker):
        """Deliberate driver-side replace (post-stage hygiene of a worker
        still mid-reply): not a death — pre-marking dead_gen keeps the
        supervisor and the death counters out of it."""
        with self._spawn_mu:
            with self._mu:
                w.dead_gen = w.generation
            w.kill()
            w.spawn()
            with self._mu:
                w.generation += 1

    def _sit_out(self, w: _Worker) -> bool:
        """Should this worker skip pulling new tasks right now? True while
        its TTL'd exclusion holds AND at least one other worker is eligible
        (the liveness guarantee: an all-excluded pool keeps serving)."""
        now = time.monotonic()
        with self._mu:
            until = self._excluded.get(w.wid)
            if until is None:
                return False
            if until <= now:
                del self._excluded[w.wid]
                return False
            for other in self.workers:
                if other is w:
                    continue
                if other.proc is None or other.proc.poll() is not None:
                    continue
                o_until = self._excluded.get(other.wid)
                if o_until is None or o_until <= now:
                    return True  # someone else can make progress
            return False

    def excluded_workers(self) -> Dict[int, float]:
        """wid -> seconds of exclusion remaining (test/debug view)."""
        now = time.monotonic()
        with self._mu:
            return {wid: round(until - now, 3)
                    for wid, until in self._excluded.items() if until > now}

    # -- scheduling -----------------------------------------------------------

    def run_tasks(self, task_msgs: List[dict],
                  shared: Optional[dict] = None,
                  cancel=None, on_task_error=None) -> List[dict]:
        """Run every task to completion (unordered internally, ordered
        results); failed/lost tasks retry on a (re)spawned worker.
        ``shared`` (stage-level resources) ships ONCE per worker, not per
        task message. ``cancel`` (a CancelToken) is polled in the scheduling
        loops: on cancel no new tasks dispatch, and workers still mid-task
        are killed by the post-stage reset — a cancelled query stops its map
        stage at the PROCESS level, not after the stage drains.
        ``on_task_error(reply) -> bool`` sees every failed reply first; a
        True return means the caller repaired the task's inputs (lineage
        recovery of a missing upstream map output) and the task re-queues
        WITHOUT consuming retry budget (bounded per task)."""
        with self._stage_mu:
            self._stage_active = True
            try:
                return self._run_tasks_locked(task_msgs, shared, cancel,
                                              on_task_error)
            finally:
                self._stage_active = False

    def _run_tasks_locked(self, task_msgs, shared, cancel, on_task_error):
        pending: "queue.Queue" = queue.Queue()
        for i, msg in enumerate(task_msgs):
            pending.put((i, msg, 0))
        results: Dict[int, dict] = {}
        errors: List[str] = []
        broken: List[str] = []
        done = threading.Event()
        deaths_at_start = self.deaths_total
        recoveries: Dict[int, int] = {}  # task -> lineage-recovery requeues
        timeout_s = float(getattr(self.conf, "task_timeout_s", 0.0) or 0.0)

        def push_shared(w: _Worker):
            if shared is not None:
                send_msg(w.sock, {"set_shared": shared})
                recv_msg(w.sock)

        def check_breaker() -> bool:
            stage_deaths = self.deaths_total - deaths_at_start
            if stage_deaths > self.max_worker_deaths:
                if not broken:
                    broken.append(
                        f"circuit breaker open: {stage_deaths} worker "
                        f"deaths in one stage (> fault_max_worker_deaths="
                        f"{self.max_worker_deaths})")
                done.set()
                return True
            return False

        outstanding: Dict[int, tuple] = {}  # i -> (msg, started_at)
        speculated: set = set()
        healthy: set = set()  # wids that proved healthy this stage (decay)
        out_mu = threading.Lock()

        def steal_speculative():
            """Idle worker + empty queue: launch ONE speculative copy of a
            long-outstanding task (straggler speculation, Spark-style but
            time-gated rather than quantile-gated; safe because both shuffle
            files and the RSS pushes publish atomically per attempt; first
            completion wins, speculative failures are ignored)."""
            now = time.monotonic()
            with out_mu:
                for i, (msg, t0) in outstanding.items():
                    if i not in results and i not in speculated and \
                            now - t0 >= self.speculation_min_s:
                        speculated.add(i)
                        return (i, msg, _SPECULATIVE)
            return None

        def serve(w: _Worker):
            # a slot that died in an earlier stage and hasn't respawned yet
            # (sock=None): bring it up before first use. The check runs
            # under _spawn_mu so a concurrent spawner's half-built worker
            # (socket accepted, hello not yet consumed) is never visible —
            # two readers on one channel would tear the frame stream.
            with self._spawn_mu:
                sock_dead = w.sock is None
            if sock_dead:
                try:
                    self._respawn(w, abort=done)
                except Exception as exc:
                    log.error("respawn of worker %d failed: %s", w.wid, exc)
                    return
                if w.sock is None:
                    return  # aborted (stage already over) or spawn failed
            try:
                push_shared(w)
            except (EOFError, OSError):
                self._note_death(w, "push_shared")
                if check_breaker() or done.is_set():
                    return
                try:
                    w.kill()
                    self._respawn(w, abort=done)
                    if done.is_set() or w.sock is None:
                        return
                    push_shared(w)
                except Exception:
                    return
            while not done.is_set():
                if cancel is not None and cancel.cancelled:
                    done.set()
                    return
                if self._sit_out(w):
                    time.sleep(0.05)
                    continue
                try:
                    i, msg, attempt = pending.get(timeout=0.1)
                except queue.Empty:
                    spec = steal_speculative()
                    if spec is None:
                        continue
                    i, msg, attempt = spec
                    log.info("speculatively re-running task %d", i)
                if attempt != _SPECULATIVE:
                    with out_mu:
                        outstanding[i] = (msg, time.monotonic())
                w.in_flight = True
                w.current_task = (i, attempt, time.monotonic())
                try:
                    send_msg(w.sock, msg)
                    reply = recv_msg(w.sock)
                    w.tasks_done_gen += 1
                except (EOFError, OSError) as exc:
                    if done.is_set():
                        return  # stage over (e.g. channel reset); stand down
                    # worker lost mid-task: respawn and retry elsewhere
                    log.warning("worker %d lost running task %d (%s)",
                                w.wid, i, exc)
                    self._note_death(w, "mid_task", task=i)
                    if attempt != _SPECULATIVE:
                        self._retry_or_fail(pending, errors, done, i, msg,
                                            attempt, f"worker lost: {exc}",
                                            results)
                    if check_breaker():
                        return
                    try:
                        w.kill()  # closes the dead channel NOW; poll() lags
                        self._respawn(w, abort=done)
                        if done.is_set() or w.sock is None:
                            # stage ended while we were respawning: pushing
                            # now would interleave with the NEXT stage's
                            # frames on this socket — stand down instead
                            return
                        push_shared(w)
                        continue
                    except Exception as spawn_exc:  # pool shrinks
                        log.error("respawn failed: %s", spawn_exc)
                        return
                finally:
                    w.in_flight = False
                    w.current_task = None
                if reply.get("ok"):
                    if w.wid not in healthy:
                        # a respawned slot that completes a task has proved
                        # itself: decay its death count (once per stage) so
                        # chaos kills don't escalate respawn backoff forever.
                        # Crash-looping slots never complete, so their
                        # backoff still grows unboundedly.
                        healthy.add(w.wid)
                        with self._mu:
                            if self._death_counts.get(w.wid, 0) > 0:
                                self._death_counts[w.wid] -= 1
                    # first completion wins; merge its registry deltas into
                    # the driver registry exactly once (a losing speculative
                    # copy's deltas are discarded — counting both would
                    # double-book the stage's spill/shuffle volume)
                    first = results.setdefault(i, reply) is reply
                    if first and reply.get("telemetry"):
                        try:
                            get_registry().merge_deltas(reply["telemetry"])
                        except Exception:
                            log.warning("telemetry merge failed for task %d",
                                        i, exc_info=True)
                    if len(results) == len(task_msgs):
                        done.set()
                elif attempt == _SPECULATIVE or i in results:
                    pass  # speculative copies never consume retry budget
                else:
                    log.warning("task %d failed on worker %d: %s",
                                i, w.wid, reply.get("error"))
                    if reply.get("error_kind") == "spill_failed":
                        # typed resource exhaustion: a retry would spill
                        # into the same full disk from another worker —
                        # fail the owning query fast and leave the
                        # (healthy) fleet to the next query
                        errors.append(
                            f"task {i}: {reply.get('error', 'spill failed')}")
                        done.set()
                        continue
                    recovered = False
                    if on_task_error is not None and recoveries.get(i, 0) < 3:
                        try:
                            recovered = bool(on_task_error(reply))
                        except Exception:
                            log.warning("task-error callback failed for "
                                        "task %d", i, exc_info=True)
                    if recovered:
                        # inputs repaired (lineage recompute): requeue at the
                        # SAME attempt — recovery is bounded by `recoveries`,
                        # not the retry budget
                        recoveries[i] = recoveries.get(i, 0) + 1
                        _TM_TASKS_RETRIED.inc()
                        pending.put((i, msg, attempt))
                    else:
                        self._retry_or_fail(pending, errors, done, i, msg,
                                            attempt,
                                            reply.get("error", "unknown"),
                                            results)

        threads = [threading.Thread(target=serve, args=(w,), daemon=True)
                   for w in self.workers]
        for t in threads:
            t.start()
        while not done.wait(0.1):
            if cancel is not None and cancel.cancelled:
                done.set()
                break
            if timeout_s > 0:
                # hard per-task timeout ON TOP of speculation: speculation
                # only helps when one copy is slow — when the original AND
                # its speculative copy both hang, each attempt trips this
                # monitor independently. There is no in-band way to
                # interrupt a wedged task, so cancellation happens at the
                # process level: the kill fails the serve thread's recv,
                # which charges the retry budget (_retry_or_fail), reroutes
                # the task, and marks the hung-but-heartbeating worker
                # suspect via the death/exclusion path (_note_death).
                now = time.monotonic()
                for w in self.workers:
                    cur = w.current_task
                    if cur is None:
                        continue
                    ti, attempt, t0 = cur
                    # cold-start grace: the first task of a fresh process
                    # pays JIT compile/setup, which the steady-state bound
                    # would misread as a hang (startup-probe vs liveness-
                    # probe distinction)
                    bound = timeout_s * (3.0 if w.tasks_done_gen == 0
                                         else 1.0)
                    if now - t0 < bound:
                        continue
                    w.current_task = None  # one kill per hung attempt
                    _TM_TASKS_TIMED_OUT.inc()
                    log.warning(
                        "task %d (attempt %s) on worker %d exceeded "
                        "task_timeout_s=%.1fs; killing the worker to "
                        "cancel it", ti,
                        "spec" if attempt == _SPECULATIVE else attempt,
                        w.wid, timeout_s)
                    try:
                        self.kill_worker(w.wid)
                    except Exception:
                        log.warning("timeout kill of worker %d failed",
                                    w.wid, exc_info=True)
            if not any(t.is_alive() for t in threads):
                # every serve thread gave up (unrespawnable workers): fail
                # the stage instead of waiting forever on an empty fleet
                if len(results) < len(task_msgs) and not broken:
                    errors.append("all workers lost and respawns failed")
                done.set()
                break
        cancelled = cancel is not None and cancel.cancelled \
            and len(results) < len(task_msgs)
        for t in threads:
            # on cancel don't wait for in-flight replies: those workers are
            # about to be killed by the reset below. Otherwise wait long
            # enough for an in-progress spawn to land — a thread that
            # outlives this join could interleave frames with the NEXT
            # stage on the same socket (the reset below is the backstop)
            t.join(timeout=0.5 if cancelled else 15)
        # a serve thread still blocked in recv (losing speculative copy or
        # straggler original) would desynchronize this worker's
        # request/reply channel for the NEXT stage. Poison the channel
        # FIRST: shutdown() wakes a blocked recv with EOF immediately and
        # the thread stands down through its done-is-set check — then join
        # so the thread is provably gone, then replace the worker. The old
        # socket object dies with the thread, so a leaked thread can never
        # consume the next query's reply off the respawned channel.
        for w, t in zip(self.workers, threads):
            if t.is_alive() or getattr(w, "in_flight", False):
                sock = w.sock
                if sock is not None:
                    try:
                        sock.shutdown(socket.SHUT_RDWR)
                    except OSError:
                        pass
                t.join(timeout=5)
                if t.is_alive():
                    log.error("serve thread for worker %d survived channel "
                              "poisoning; worker will be replaced anyway",
                              w.wid)
                try:
                    self._reset_worker(w)
                except Exception as exc:
                    log.error("post-stage worker reset failed: %s", exc)
        if cancelled:
            from blaze_tpu.ops.base import QueryCancelled

            raise QueryCancelled(cancel.reason or "cancelled")
        if broken:
            raise WorkerPoolBroken("; ".join(broken + errors))
        if errors:
            raise TaskFailed("; ".join(errors))
        return [results[i] for i in range(len(task_msgs))]

    def _retry_or_fail(self, pending, errors, done, i, msg, attempt, reason,
                       results):
        if i in results:
            return  # another (speculative) attempt already completed
        if attempt + 1 <= self.max_task_retries:
            _TM_TASKS_RETRIED.inc()
            pending.put((i, msg, attempt + 1))
        else:
            errors.append(f"task {i}: {reason} (after {attempt + 1} attempts)")
            done.set()

    # -- lifecycle ------------------------------------------------------------

    def kill_worker(self, wid: int) -> Optional[int]:
        """Chaos/test hook: hard-kill one worker process (simulates executor
        loss). Detection, counting and respawn happen through the normal
        supervision paths. Returns the killed pid."""
        w = self.workers[wid]
        pid = w.proc.pid if w.proc is not None else None
        if w.proc is not None:
            w.proc.kill()
        return pid

    def close(self):
        self._closed.set()
        if self._supervisor.is_alive():
            self._supervisor.join(timeout=5)
        for w in self.workers:
            try:
                if w.sock is not None:
                    send_msg(w.sock, {"shutdown": True})
            except OSError:
                pass
            w.kill()
        self.listener.close()
        try:
            os.unlink(self.sock_path)
            os.rmdir(self._sockdir)
        except OSError:
            pass
