"""Replicated serving: N ServeEngines behind one coordinator, with
checkpointed failover that is bit-identical to the fault-free run.

The paper's O(1)-in-context decode state is what makes replica failover
cheap enough to do synchronously: a slot's whole decode state is a
constant-size snapshot (r^2 x (h+1) per kv-head for polysketch, the
recurrent state for SSM/RG-LRU), so the coordinator can checkpoint every
live slot at block boundaries into the shared `PrefixCache` side-store
for the cost of one small d2h copy — no paged KV migration, no O(context)
state transfer. When a replica dies, each of its in-flight requests is
re-homed on a survivor: restore the deepest usable checkpoint, replay the
few tokens past it through the decode path, and continue. The recovered
stream is bit-identical to what the dead replica would have produced
(engine.`_install_recovery` holds that contract; tests/test_replicas.py
locks it per state family).

Coordinator responsibilities:
  - route `submit()` to the least-loaded live replica (global request
    ids; the per-replica rid is an internal detail),
  - keep a host mirror of every live request's observed token stream —
    the recovery source of truth; it advances only on a replica's
    SUCCESSFUL tick, so a dying tick's outputs are discarded atomically
    (no token is ever reported twice, none is lost),
  - checkpoint live slots on the block grid (x `checkpoint_blocks`),
  - watch per-replica tick health: a `StragglerDetector` per replica
    (z-score flags), an optional hard hang timeout, and heartbeats,
  - shed load: `submit()` raises `Overloaded` past
    `shed_above x live_replicas` outstanding requests — admission
    control degrades before latency does,
  - arm a `ChaosInjector` (serve/chaos.py) for fault drills: kills,
    hangs, slow ticks, dropped checkpoints, flaky disk.
"""
from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass, field
from dataclasses import replace as dc_replace

import jax
import numpy as np
from jax.sharding import Mesh

from repro.distributed.fault import StragglerDetector
from repro.serve.chaos import ChaosInjector, ReplicaKilled
from repro.serve.engine import (RecoveredRequest, RequestOutput,
                                SamplingParams, ServeEngine)
from repro.serve.plan import ServePlan
from repro.serve.prefix_cache import PrefixCache
from repro.serve.telemetry import Telemetry


class Overloaded(RuntimeError):
    """submit() refused: the fleet is past its load-shedding threshold."""


def replica_plans(n_replicas: int, *, model_parallel: int = 1
                  ) -> list[ServePlan]:
    """One ServePlan per replica. With enough devices each replica gets
    its own disjoint (1 x model_parallel) mesh slice — a real fault
    domain; otherwise every replica runs the trivial single-device plan
    (the CPU test topology, where replicas are fault-isolation units in
    the coordinator's bookkeeping only)."""
    devs = jax.devices()
    need = n_replicas * model_parallel
    if len(devs) >= need and need > n_replicas:
        out = []
        for i in range(n_replicas):
            sl = np.asarray(devs[i * model_parallel:(i + 1) * model_parallel])
            out.append(ServePlan.from_mesh(
                Mesh(sl.reshape(1, model_parallel), ("data", "model")),
                shard_model=model_parallel > 1))
        return out
    if len(devs) >= n_replicas:
        return [ServePlan.from_mesh(
            Mesh(np.asarray(devs[i:i + 1]).reshape(1, 1),
                 ("data", "model")))
            for i in range(n_replicas)]
    return [ServePlan.single_device() for _ in range(n_replicas)]


@dataclass
class _GReq:
    """Coordinator mirror of one live request: everything needed to
    re-create it on a survivor, advanced only on successful ticks."""
    grid: int
    prompt: np.ndarray
    max_new: int
    eos: int | None
    sampling: SamplingParams
    submit_time: float
    replica: int
    lrid: int                       # rid on its current home engine
    emitted: list[int] = field(default_factory=list)
    lps: list[float] = field(default_factory=list)
    ttft_s: float = 0.0
    ckpt_pos: int = 0               # deepest checkpointed stream depth
    recovered: int = 0              # failovers survived


class ReplicaSet:
    """N replicated ServeEngines, one shared PrefixCache, bit-exact
    failover. See the module docstring for the design; the external
    surface mirrors a single engine: `submit` / `step` / `run` /
    `busy` / `stats` / `reset_stats`, with global request ids."""

    def __init__(self, model, cfg, params, *, n_replicas: int = 2,
                 slots: int = 4, max_len: int = 4096,
                 prefix_cache: PrefixCache | None = None,
                 min_snapshot_blocks: int = 1,
                 logprobs: bool = False,
                 prefill_budget: int | None = None,
                 overlap: bool = False,
                 checkpoint_blocks: int = 1,
                 hang_timeout_s: float | None = None,
                 shed_above: int | None = None,
                 evict_after_flags: int | None = None,
                 chaos: ChaosInjector | None = None,
                 telemetry: Telemetry | None = None,
                 plans: list[ServePlan] | None = None,
                 engine_telemetry=None):
        if n_replicas < 1:
            raise ValueError("need at least one replica")
        if checkpoint_blocks < 1:
            raise ValueError("checkpoint_blocks must be >= 1")
        self.n = n_replicas
        self.cache = prefix_cache
        self.checkpoint_blocks = checkpoint_blocks
        self.hang_timeout_s = hang_timeout_s
        self.shed_above = shed_above
        self.evict_after_flags = evict_after_flags
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self.chaos = chaos
        if plans is None:
            plans = replica_plans(n_replicas)
        if len(plans) != n_replicas:
            raise ValueError(f"{len(plans)} plans for {n_replicas} replicas")
        mk_tel = engine_telemetry or (lambda i: Telemetry())
        self.engines: list[ServeEngine | None] = [
            ServeEngine(model, cfg, params, slots=slots, max_len=max_len,
                        prefix_cache=prefix_cache,
                        min_snapshot_blocks=min_snapshot_blocks,
                        logprobs=logprobs, prefill_budget=prefill_budget,
                        overlap=overlap, telemetry=mk_tel(i), plan=plans[i])
            for i in range(n_replicas)]
        self._alive = [True] * n_replicas
        self._ticks = [0] * n_replicas
        self._beats = [time.monotonic()] * n_replicas
        self._stragglers = [StragglerDetector() for _ in range(n_replicas)]
        self._grace: set[int] = set()  # survivors' next tick installs a
        # recovery (fresh compiles, possibly seconds): exempt that one
        # tick from the hang deadline and the straggler window, or a
        # single failover would cascade through the whole fleet
        self._live: dict[int, _GReq] = {}      # grid -> mirror
        self._rmap: dict[tuple[int, int], int] = {}  # (replica, lrid) -> grid
        self._done: set[int] = set()
        self._next_grid = 0
        self.finished: list[RequestOutput] = []
        self._deaths: dict[str, int] = {}
        # one "replicaI cause: Type: message" line per death — a crash
        # nobody injected must be readable, not just counted
        self._death_log: list[str] = []
        self._n_failovers = 0
        self._n_ckpts = 0
        self._n_ckpt_dropped = 0
        self._n_shed = 0
        self._n_dups = 0               # dedup guard trips (must stay 0)

        if chaos is not None:
            chaos.arm(n_replicas)
            hook = chaos.io_fault_hook()
            if hook is not None and prefix_cache is not None:
                prefix_cache.io_fault = hook

        reg = self.telemetry.registry
        reg.counter("serve_replica_deaths_total", "replica deaths",
                    fn=lambda: float(sum(self._deaths.values())))
        reg.counter("serve_replica_failovers_total",
                    "requests re-homed after a replica death",
                    fn=lambda: float(self._n_failovers))
        reg.counter("serve_replica_checkpoints_total",
                    "slot checkpoints written to the shared cache",
                    fn=lambda: float(self._n_ckpts))
        reg.counter("serve_replica_shed_total",
                    "submissions refused by the load-shedding gate",
                    fn=lambda: float(self._n_shed))
        reg.gauge("serve_replicas_alive", "live replicas",
                  fn=lambda: float(sum(self._alive)))
        reg.gauge("serve_replica_outstanding", "live requests fleet-wide",
                  fn=lambda: float(len(self._live)))
        tr = self.telemetry.tracer
        if tr:
            for i in range(n_replicas):
                # lifetime span: stays open while the replica lives
                # (export tags it `unterminated`), ended at death
                tr.begin(f"replica{i}", "replica",
                         mesh=plans[i].describe())

    # -- routing -----------------------------------------------------------

    def _outstanding(self, i: int) -> int:
        return sum(g.replica == i for g in self._live.values())

    def _least_loaded(self) -> int:
        cands = [i for i in range(self.n) if self._alive[i]]
        if not cands:
            raise RuntimeError("all replicas dead")
        return min(cands, key=lambda i: (self._outstanding(i), i))

    def submit(self, prompt, max_new_tokens: int, eos_id: int | None = None,
               sampling: SamplingParams | None = None) -> int:
        """Enqueue on the least-loaded live replica; returns the GLOBAL
        request id. Raises `Overloaded` past the shedding threshold —
        the caller owns backpressure (retry later, or 429 upstream)."""
        if self.shed_above is not None:
            limit = self.shed_above * sum(self._alive)
            if len(self._live) >= limit:
                self._n_shed += 1
                tr = self.telemetry.tracer
                if tr:
                    tr.instant("queue", "shed", outstanding=len(self._live),
                               limit=limit)
                raise Overloaded(
                    f"{len(self._live)} outstanding >= shed limit {limit} "
                    f"({self.shed_above} x {sum(self._alive)} live replicas)")
        i = self._least_loaded()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        sp = sampling or SamplingParams()
        lrid = self.engines[i].submit(prompt, max_new_tokens, eos_id, sp)
        grid = self._next_grid
        self._next_grid += 1
        g = _GReq(grid=grid, prompt=prompt, max_new=max_new_tokens,
                  eos=eos_id, sampling=sp, submit_time=time.perf_counter(),
                  replica=i, lrid=lrid)
        self._live[grid] = g
        self._rmap[(i, lrid)] = grid
        return grid

    # -- checkpointing -----------------------------------------------------

    @staticmethod
    def _tag(grid: int) -> bytes:
        # failover checkpoints are keyed per REQUEST, never by content:
        # decode-produced state is not bitwise-interchangeable with
        # prefill-produced state, so these entries must stay out of the
        # content-addressed prefix keyspace (PrefixCache keeps them in a
        # separate side-store)
        return hashlib.sha256(b"psk-ckpt:%d" % grid).digest()

    def _checkpoint(self, i: int, tick: int):
        eng = self.engines[i]
        if self.cache is None or eng.state.snapshot_granularity is None:
            return
        grid_step = eng.state.block_size * self.checkpoint_blocks
        for si in range(eng.slots):
            slot = eng._slots[si]
            if not slot.decoding:
                continue
            grid = self._rmap.get((i, slot.request.rid))
            g = self._live.get(grid) if grid is not None else None
            if g is None:
                continue
            covered = eng.slot_covered(si)
            if covered % grid_step != 0 or covered <= g.ckpt_pos:
                continue
            if self.chaos is not None and self.chaos.drops_snapshot(i, tick):
                self._n_ckpt_dropped += 1
                continue
            snap = eng.snapshot_slot(si)
            if snap is None:
                continue
            self.cache.put_ckpt(self._tag(g.grid), covered, snap[0])
            g.ckpt_pos = covered
            self._n_ckpts += 1
            tr = self.telemetry.tracer
            if tr:
                tr.instant(f"replica{i}", "checkpoint", grid=g.grid,
                           n_tokens=covered)

    # -- failure handling --------------------------------------------------

    def _fail(self, i: int, cause: str, error: BaseException | None = None):
        """Replica i is dead: discard it atomically (its un-mirrored tick
        never happened) and re-home every request it owned onto the
        survivors, deepest-checkpoint first. `error` is the exception the
        replica's tick raised, kept in the death log."""
        self._alive[i] = False
        self._beats[i] = time.monotonic()
        self._deaths[cause] = self._deaths.get(cause, 0) + 1
        self._death_log.append(
            f"replica{i} {cause}"
            + (f": {type(error).__name__}: {error}" if error else ""))
        tr = self.telemetry.tracer
        if tr:
            tr.instant(f"replica{i}", "replica_dead", cause=cause)
            tr.end(f"replica{i}", cause=cause)  # lifetime span
        victims = sorted((g for g in self._live.values() if g.replica == i),
                         key=lambda g: g.grid)
        # release the dead engine's device state before recovery prefills
        self.engines[i] = None
        if not any(self._alive):
            raise RuntimeError(
                f"all {self.n} replicas dead (last cause: {cause}); "
                f"{len(victims)} requests unrecoverable")
        for g in victims:
            self._rmap.pop((i, g.lrid), None)
            j = self._least_loaded()
            k = len(g.emitted)
            ckpt, ck_n = None, 0
            if self.cache is not None and k > 0:
                got = self.cache.get_ckpt(
                    self._tag(g.grid),
                    max_tokens=int(g.prompt.shape[0]) + k - 1)
                if got is not None:
                    ckpt, ck_n = got
            rec = RecoveredRequest(
                prompt=g.prompt, emitted=list(g.emitted), lps=list(g.lps),
                max_new_tokens=g.max_new, eos_id=g.eos, sampling=g.sampling,
                submit_time=g.submit_time, ttft_s=g.ttft_s,
                snapshot=ckpt, snap_tokens=ck_n)
            if tr:
                tr.begin(f"replica{j}", "recover", grid=g.grid,
                         emitted=k, from_ckpt=ck_n)
            lrid = self.engines[j].admit_recovered(rec)
            if tr:
                tr.end(f"replica{j}")
                tr.instant(f"replica{j}", "failover", grid=g.grid,
                           from_replica=i)
            g.replica, g.lrid = j, lrid
            g.recovered += 1
            self._rmap[(j, lrid)] = g.grid
            self._n_failovers += 1
            self._grace.add(j)

    # -- the coordinator tick ----------------------------------------------

    def step(self) -> list[RequestOutput]:
        """One tick of every live replica. A replica that raises, hangs
        past `hang_timeout_s`, or trips the straggler-eviction threshold
        dies HERE, and its requests fail over before the method returns —
        the caller never sees a lost request, only (eventually) its
        outputs under their global ids."""
        done: list[RequestOutput] = []
        for i in range(self.n):
            if not self._alive[i]:
                continue
            eng = self.engines[i]
            tick = self._ticks[i]
            jit_pre = sum(eng.telemetry.watchdog.cache_sizes().values())
            t0 = time.perf_counter()
            try:
                if self.chaos is not None:
                    self.chaos.before_tick(i, tick)
                outs = eng.step()
            except Exception as e:  # noqa: BLE001 — any tick failure is a death
                self._fail(i, "kill" if isinstance(e, ReplicaKilled)
                           else "crash", e)
                continue
            dt = time.perf_counter() - t0
            self._ticks[i] += 1
            # a tick that grew a jit cache spent its time COMPILING (cold
            # admission, recovery install): a compile stall is not a hang
            # and must not poison the straggler window either, or every
            # fresh fleet would evict itself on its first admissions
            compiled = (sum(eng.telemetry.watchdog.cache_sizes().values())
                        > jit_pre)
            graced = (i in self._grace) or compiled
            self._grace.discard(i)
            if (self.hang_timeout_s is not None and not graced
                    and dt > self.hang_timeout_s):
                # the tick "finished" but blew the deadline: treat as a
                # hang-death and DISCARD outs — the mirror was not
                # advanced, so recovery regenerates exactly these tokens
                self._fail(i, "hang")
                continue
            slow = (False if graced else self._stragglers[i].observe(dt))
            if (slow and self.evict_after_flags is not None
                    and len(self._stragglers[i].flagged)
                    >= self.evict_after_flags):
                self._fail(i, "straggler")
                continue
            self._beats[i] = time.monotonic()
            # SUCCESS: advance the mirror (engine host view is always >=
            # the mirror — slots are pre-seeded on recovery), checkpoint,
            # then report finished requests under their global ids
            for entry in eng.live_requests():
                grid = self._rmap.get((i, entry["rid"]))
                g = self._live.get(grid) if grid is not None else None
                if g is None or len(entry["emitted"]) < len(g.emitted):
                    continue
                g.emitted = entry["emitted"]
                g.lps = entry["lps"]
                if entry["ttft_s"]:
                    g.ttft_s = entry["ttft_s"]
            self._checkpoint(i, tick)
            for o in outs:
                grid = self._rmap.pop((i, o.rid), None)
                if grid is None or grid in self._done:
                    self._n_dups += 1
                    continue
                self._live.pop(grid, None)
                self._done.add(grid)
                if self.cache is not None:
                    self.cache.drop_ckpt(self._tag(grid))
                out = dc_replace(o, rid=grid)
                self.finished.append(out)
                done.append(out)
        self.telemetry.on_tick()
        return done

    @property
    def busy(self) -> bool:
        return bool(self._live) or any(
            self._alive[i] and self.engines[i].busy for i in range(self.n))

    def run(self) -> list[RequestOutput]:
        out = []
        while self.busy:
            out.extend(self.step())
        return out

    # -- drain / accounting ------------------------------------------------

    def drain_checkpoints(self) -> list[str]:
        """Graceful-shutdown persistence (SIGTERM path): each live
        replica stops admissions and runs out at most one block of extra
        ticks so every live slot reaches a snapshot boundary
        (ServeEngine.drain_checkpoints), then the shared side-store is
        flushed to the cache's disk tier once. Returns written paths."""
        for i in range(self.n):
            if self._alive[i]:
                self.engines[i].drain_checkpoints(
                    tag_ns=b"psk-drain:%d" % i, flush=False)
        if self.cache is not None and self.cache.save_dir is not None:
            return self.cache.flush_ckpts_to_disk()
        return []

    def reset_stats(self):
        """Post-warm-up zeroing, mirroring ServeEngine.reset_stats."""
        self.finished = []
        self._done = set()
        self._deaths = {}
        self._death_log = []
        self._n_failovers = self._n_ckpts = self._n_ckpt_dropped = 0
        self._n_shed = self._n_dups = 0
        self.telemetry.reset()
        for i in range(self.n):
            if self._alive[i]:
                self.engines[i].reset_stats()

    def stats(self) -> dict:
        live = [i for i in range(self.n) if self._alive[i]]
        return {
            "replicas": self.n,
            "alive": sum(self._alive),
            "deaths": dict(self._deaths),
            "death_log": list(self._death_log),
            "failovers": self._n_failovers,
            "checkpoints": self._n_ckpts,
            "checkpoints_dropped": self._n_ckpt_dropped,
            "shed": self._n_shed,
            "duplicate_outputs": self._n_dups,  # must stay 0
            "live_requests": len(self._live),
            "requests": len(self.finished),
            "recovered_installs": sum(
                int(self.engines[i].stats()["recovered"]) for i in live),
            "straggler_flags": [len(self._stragglers[i].flagged)
                                for i in range(self.n)],
            "heartbeat_age_s": [round(time.monotonic() - b, 3)
                                for b in self._beats],
            # steady-state retraces across SURVIVORS (the CI failover gate:
            # recovery re-arms each engine's baseline, so growth here is a
            # real mid-serve recompile)
            "retraces": sum(
                self.engines[i].telemetry.watchdog.retraces for i in live),
            "engines": {i: self.engines[i].stats() for i in live},
        }
