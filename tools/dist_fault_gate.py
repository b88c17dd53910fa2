#!/usr/bin/env python
"""Distributed fault-tolerance CI gate (run_tests.sh; skippable via
PADDLE_TPU_SKIP_DIST_FAULT_GATE=1).

In the crash/serving-gate mold, but MULTI-PROCESS: real worker
processes over the real socket TCPStore, proving the PR-11 acceptance
criteria end to end (docs/distributed_faults.md):

  1. kill-a-rank mid-collective -> every survivor raises a typed
     PeerLostError NAMING the dead rank within 2x the failure-detector
     TTL (not the 3600 s p2p timeout), then re-rendezvouses with the
     survivor set and keeps exchanging;
  2. restart-with-stale-keys    -> a rank that dies mid-collective
     (payload posted, completion never reached) and rejoins with a
     RESET sequence counter can never consume the prior generation's
     keys (generation-scoped namespaces), and the rendezvous leader
     sweeps every stale-generation key;
  3. store-outage storm         -> randomized bursts of injected
     store-op failures (several seeds) are fully absorbed by the
     bounded jittered-backoff retry — every exchange round correct —
     while a PERSISTENT outage escalates to the typed
     StoreUnavailableError;
  4. kill -> elastic restart -> bitwise resume: gpt_tiny+AdamW under
     run_elastic through the elastic launcher; rank 1 is killed
     mid-run, relaunched, and the job converges to EXACTLY the
     uninterrupted run's losses and parameter digest on every rank
     (the PR-4 resume invariant extended across a rank loss).

Every scenario also asserts EXACT store key accounting: after drain,
zero ``obj/`` payload or ``__barrier__/`` keys of ANY generation remain
on the master store.

Exit codes: 0 ok, 1 a fault-tolerance invariant was violated.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

TTL = 1.5  # failure-detector TTL used by every scenario (seconds)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _worker_env(rank: int, world: int, port: int, **extra) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO_ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                        if p])
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_MASTER": f"127.0.0.1:{port}",
        "PADDLE_TPU_NO_JAX_DIST": "1",
        "GATE_TTL": str(TTL),
    })
    # unconditionally: the workers exercise the host-side control plane,
    # and tools/tpu_smoke.py spawns them from a process that holds the
    # chip — an inherited JAX_PLATFORMS=tpu would send every one of them
    # after it
    env["JAX_PLATFORMS"] = "cpu"
    env.update({k: str(v) for k, v in extra.items()})
    return env


def _spawn(script: str, rank: int, world: int, port: int, **extra):
    return subprocess.Popen(
        [sys.executable, "-u", script], env=_worker_env(rank, world, port,
                                                        **extra),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=_REPO_ROOT)


def _finish(procs: dict, timeout: float = 300.0) -> dict:
    """Wait for every worker; returns {rank: (rc, output)}."""
    out = {}
    deadline = time.monotonic() + timeout
    for rank, p in procs.items():
        try:
            o, _ = p.communicate(timeout=max(1.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            p.kill()
            o, _ = p.communicate()
            o = (o or "") + "\n<GATE: worker timed out>"
        out[rank] = (p.returncode, o or "")
    return out


_PRELUDE = r"""
import os, sys, time, pickle
rank = int(os.environ["PADDLE_TRAINER_ID"])
TTL = float(os.environ["GATE_TTL"])
import paddle_tpu.distributed as D
from paddle_tpu.distributed import env as E, fault_tolerance as ft
from paddle_tpu.distributed.errors import (
    PeerLostError, RendezvousInvalidated, StoreUnavailableError)
from paddle_tpu.distributed.fleet.elastic import ElasticManager
E.init_parallel_env()
store = E.get_store()
assert store is not None, "rendezvous store missing"


def leak_keys():
    # every collective payload/barrier key of ANY generation; the
    # bring-up barriers (init_parallel_env/launch, sweep=False by
    # design) are the only __barrier__ names outside the g<gen>/
    # namespace and are intentionally persistent
    return [k for k in store.keys()
            if "/obj/" in k or k.startswith("__barrier__/g")]
"""


# ---------------------------------------------------------------------------
# 1. kill a rank mid-collective
# ---------------------------------------------------------------------------

_KILL_WORKER = _PRELUDE + r"""
mgr = ElasticManager(store, rank=rank, nnodes=3, min_nodes=2, ttl=TTL,
                     interval=0.25)
mgr.start()
g1, mem = ft.rendezvous(store, mgr, rank, timeout=90)
objs = []
D.all_gather_object(objs, ("r1", rank))
assert sorted(objs) == [("r1", 0), ("r1", 1), ("r1", 2)], objs
if rank == 2:
    os._exit(1)          # die mid-job: survivors are entering round 2
t0 = time.monotonic()
try:
    objs = []
    D.all_gather_object(objs, ("r2", rank))
    print("GATE_FAIL round-2 exchange returned", objs)
    sys.exit(1)
except PeerLostError as e:
    el = time.monotonic() - t0
    assert e.ranks == [2], f"wrong ranks named: {e.ranks}"
    assert el <= 2.0 * TTL, f"detection took {el:.2f}s > 2xTTL={2*TTL}"
    print(f"PEER_LOST ranks={e.ranks} elapsed={el:.2f}", flush=True)
# let EVERY survivor observe the loss before anyone re-rendezvouses (the
# request bump would otherwise turn a slow survivor's PeerLostError into
# RendezvousInvalidated — also typed, but scenario 1 proves detection)
time.sleep(2.0 * TTL)
g2, mem2 = ft.rendezvous(store, mgr, rank, timeout=90)
assert g2 > g1 and mem2 == [0, 1], (g2, mem2)
objs = []
D.all_gather_object(objs, ("r3", rank))
assert sorted(objs) == [("r3", 0), ("r3", 1)], objs
print(f"RECOVERED gen={g2} members={mem2}", flush=True)
D.barrier()
if rank == 0:
    time.sleep(0.8)      # let rank 1 finish its barrier departure sweep
    leak = leak_keys()
    print(f"KEYS {len(leak)} {leak[:8]}", flush=True)
mgr.stop()
print("WORKER_DONE", flush=True)
"""


def scenario_kill_rank(verbose: bool = True) -> bool:
    port = _free_port()
    with tempfile.TemporaryDirectory(prefix="dist_gate_kill_") as d:
        script = os.path.join(d, "w.py")
        with open(script, "w") as f:
            f.write(_KILL_WORKER)
        procs = {r: _spawn(script, r, 3, port) for r in range(3)}
        res = _finish(procs)
    ok = True
    for r in (0, 1):
        rc, out = res[r]
        if rc != 0 or "PEER_LOST ranks=[2]" not in out \
                or "RECOVERED" not in out or "WORKER_DONE" not in out:
            print(f"dist_fault_gate: FAIL [kill] rank {r} rc={rc}\n"
                  f"{out[-1800:]}")
            ok = False
    if res[2][0] != 1:
        print(f"dist_fault_gate: FAIL [kill] rank 2 rc={res[2][0]} "
              "(expected the injected death)")
        ok = False
    if ok and "KEYS 0" not in res[0][1]:
        print(f"dist_fault_gate: FAIL [kill] store keys leaked\n"
              f"{res[0][1][-800:]}")
        ok = False
    if ok and verbose:
        line = [ln for ln in res[0][1].splitlines()
                if ln.startswith("PEER_LOST")][0]
        print(f"dist_fault_gate: kill-a-rank OK ({line})")
    return ok


# ---------------------------------------------------------------------------
# 2. restart with stale keys
# ---------------------------------------------------------------------------

_STALE_R0 = _PRELUDE + r"""
mgr = ElasticManager(store, rank=0, nnodes=2, ttl=TTL, interval=0.25)
mgr.start()
g1, mem = ft.rendezvous(store, mgr, 0, timeout=90)
for i in (1, 2):
    objs = []
    D.all_gather_object(objs, f"r0-{i}")
    assert objs == [f"r0-{i}", f"A-{i}"], objs
try:
    objs = []
    D.all_gather_object(objs, "r0-3")   # A posted its payload, then died
    print("GATE_FAIL round-3 exchange completed", objs)
    sys.exit(1)
except (PeerLostError, RendezvousInvalidated) as e:
    print(f"ROUND3_ABORT {type(e).__name__}", flush=True)
g2, mem2 = ft.rendezvous(store, mgr, 0, timeout=120)
assert g2 > g1, (g1, g2)
for i in (1, 2):
    objs = []
    D.all_gather_object(objs, f"r0-g2-{i}")
    assert objs == [f"r0-g2-{i}", f"B-{i}"], ("stale payload consumed", objs)
D.barrier()
time.sleep(0.8)
stale = store.keys(f"g{g1}/") + store.keys(f"__barrier__/g{g1}/")
print(f"STALE {len(stale)} {stale[:6]}", flush=True)
leak = leak_keys()
print(f"KEYS {len(leak)} {leak[:8]}", flush=True)
mgr.stop()
print("WORKER_DONE", flush=True)
"""

_STALE_R1 = _PRELUDE + r"""
mgr = ElasticManager(store, rank=1, nnodes=2, ttl=TTL, interval=0.25)
mgr.start()
g, mem = ft.rendezvous(store, mgr, 1, timeout=120)
if os.environ["GATE_INCARNATION"] == "A":
    for i in (1, 2):
        objs = []
        D.all_gather_object(objs, f"A-{i}")
        assert objs == [f"r0-{i}", f"A-{i}"], objs
    # round 3: post the payload (sequence counter 3 in generation g),
    # then die before the completion barrier — the classic stale key
    store.set(f"g{g}/obj/ag/3/1", pickle.dumps("A-3"))
    os._exit(1)
# incarnation B: a FRESH process whose _OBJ_SEQ restarts at 0.  Without
# generation scoping its first rounds would read incarnation A's seq-1/2
# payloads; with it they land in the new generation's namespace.
for i in (1, 2):
    objs = []
    D.all_gather_object(objs, f"B-{i}")
    assert objs == [f"r0-g2-{i}", f"B-{i}"], ("stale payload consumed", objs)
D.barrier()
mgr.stop()
print("WORKER_DONE", flush=True)
"""


def scenario_restart_stale_keys(verbose: bool = True) -> bool:
    port = _free_port()
    with tempfile.TemporaryDirectory(prefix="dist_gate_stale_") as d:
        s0 = os.path.join(d, "r0.py")
        s1 = os.path.join(d, "r1.py")
        with open(s0, "w") as f:
            f.write(_STALE_R0)
        with open(s1, "w") as f:
            f.write(_STALE_R1)
        p0 = _spawn(s0, 0, 2, port)
        pa = _spawn(s1, 1, 2, port, GATE_INCARNATION="A")
        # incarnation A must die (rc=1) before B may join
        try:
            oa, _ = pa.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            pa.kill()
            oa, _ = pa.communicate()
        if pa.returncode != 1:
            print(f"dist_fault_gate: FAIL [stale] incarnation A rc="
                  f"{pa.returncode}\n{(oa or '')[-1200:]}")
            p0.kill()
            return False
        pb = _spawn(s1, 1, 2, port, GATE_INCARNATION="B")
        res = _finish({0: p0, 1: pb})
    ok = True
    for r in (0, 1):
        rc, out = res[r]
        if rc != 0 or "WORKER_DONE" not in out:
            print(f"dist_fault_gate: FAIL [stale] rank {r} rc={rc}\n"
                  f"{out[-1800:]}")
            ok = False
    if ok and ("STALE 0" not in res[0][1] or "KEYS 0" not in res[0][1]):
        print(f"dist_fault_gate: FAIL [stale] stale-generation keys "
              f"survived the sweep\n{res[0][1][-800:]}")
        ok = False
    if ok and verbose:
        abort = [ln for ln in res[0][1].splitlines()
                 if ln.startswith("ROUND3_ABORT")][0]
        print(f"dist_fault_gate: restart-with-stale-keys OK ({abort}, "
              "generation swept)")
    return ok


# ---------------------------------------------------------------------------
# 3. store-outage storm (randomized) + persistent outage escalation
# ---------------------------------------------------------------------------

_STORM_WORKER = _PRELUDE + r"""
import numpy as np
from paddle_tpu.faults import FaultInjector, random_store_schedule
seed = int(os.environ["GATE_SEED"])
inj = random_store_schedule(np.random.RandomState(seed + rank),
                            horizon=80, n_faults=5,
                            max_burst=3).install(store)
for i in range(10):
    objs = []
    D.all_gather_object(objs, (rank, i))
    assert objs == [(0, i), (1, i)], objs
D.barrier()
print(f"STORM_OK fired={inj.fired()}", flush=True)
if rank == 0:
    time.sleep(0.8)     # let rank 1 finish its barrier departure sweep
    leak = leak_keys()
    print(f"KEYS {len(leak)} {leak[:8]}", flush=True)
else:
    time.sleep(2.0)     # no new collectives while rank 0 audits the keys
# persistent outage: must escalate to the TYPED StoreUnavailableError
os.environ["PADDLE_STORE_RETRIES"] = "2"
os.environ["PADDLE_STORE_BACKOFF"] = "0.01"
FaultInjector().inject("store_op", at=0, times=10 ** 9,
                       kind="store_error").install(store)
try:
    objs = []
    D.all_gather_object(objs, "x")
    print("GATE_FAIL persistent outage did not escalate")
    sys.exit(1)
except StoreUnavailableError:
    print("STORE_UNAVAILABLE typed", flush=True)
print("WORKER_DONE", flush=True)
"""


def scenario_store_outage(seeds=(3, 17, 42), verbose: bool = True) -> bool:
    ok = True
    fired_total = 0
    for seed in seeds:
        port = _free_port()
        with tempfile.TemporaryDirectory(prefix="dist_gate_storm_") as d:
            script = os.path.join(d, "w.py")
            with open(script, "w") as f:
                f.write(_STORM_WORKER)
            procs = {r: _spawn(script, r, 2, port, GATE_SEED=seed)
                     for r in range(2)}
            res = _finish(procs, timeout=180)
        for r in (0, 1):
            rc, out = res[r]
            if rc != 0 or "STORM_OK" not in out \
                    or "STORE_UNAVAILABLE typed" not in out:
                print(f"dist_fault_gate: FAIL [storm seed={seed}] rank {r} "
                      f"rc={rc}\n{out[-1800:]}")
                ok = False
            else:
                fired_total += int(out.split("STORM_OK fired=")[1]
                                   .split()[0])
        if ok and "KEYS 0" not in res[0][1]:
            print(f"dist_fault_gate: FAIL [storm seed={seed}] keys leaked "
                  f"under the fault schedule\n{res[0][1][-800:]}")
            ok = False
    if ok and fired_total == 0:
        print("dist_fault_gate: FAIL [storm] no injected store fault ever "
              "fired — dead schedules prove nothing")
        ok = False
    if ok and verbose:
        print(f"dist_fault_gate: store-outage storm OK ({len(seeds)} seeds, "
              f"{fired_total} injected faults absorbed, typed escalation)")
    return ok


# ---------------------------------------------------------------------------
# 4. kill -> elastic restart -> bitwise resume (gpt_tiny + AdamW)
# ---------------------------------------------------------------------------

STEPS = 5
KILL_AT = 2

_TRAIN_SETUP = r"""
import hashlib, json
import numpy as np
import paddle_tpu as pt
from paddle_tpu.models import (
    GPTForPretraining, GPTPretrainingCriterion, gpt_tiny)

cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
_rng = np.random.RandomState(0)
ids = pt.to_tensor(_rng.randint(0, cfg.vocab_size, (2, 16)), dtype="int64")
labels = pt.to_tensor(_rng.randint(0, cfg.vocab_size, (2, 16)),
                      dtype="int64")
crit = GPTPretrainingCriterion(cfg)
pt.seed(7)
m = GPTForPretraining(cfg)
opt = pt.optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())


def sgd_step():
    loss = crit(m(ids), labels)
    loss.backward()
    opt.step()
    opt.clear_grad()
    return float(loss)


def param_digest():
    h = hashlib.sha256()
    for p in m.parameters():
        h.update(np.ascontiguousarray(np.asarray(p._value)).tobytes())
    return h.hexdigest()
"""

_ELASTIC_WORKER = _PRELUDE + _TRAIN_SETUP + r"""
from paddle_tpu.checkpoint import CheckpointManager, TrainState
from paddle_tpu.distributed.fleet.elastic import run_elastic

ckdir = os.environ["GATE_CKDIR"]
steps = int(os.environ["GATE_STEPS"])
kill_at = int(os.environ["GATE_KILL_AT"])
marker = os.path.join(ckdir, "killed_once")
mgr = ElasticManager(store, rank=rank, nnodes=2, ttl=TTL, interval=0.3)
mgr.start()
ck = CheckpointManager(os.path.join(ckdir, f"rank{rank}"), keep_last_k=50)


def train_fn(step):
    # host-side membership sync FIRST: a peer death lands the survivor
    # inside a collective (the PeerLostError path), and the torn step
    # aborts before any model/optimizer mutation
    objs = []
    D.all_gather_object(objs, ("sync", step))
    if rank == 1 and step == kill_at and not os.path.exists(marker):
        open(marker, "w").close()
        os._exit(1)      # SIGKILL-grade death; the launcher relaunches us
    return sgd_step()


res = run_elastic(train_fn, mgr, ck, TrainState(m, opt), total_steps=steps,
                  store=store, save_every=1, rendezvous_timeout=300.0)
print("LOSSES", json.dumps(res.results), flush=True)
print(f"DIGEST {param_digest()} RECOVERIES {res.recoveries}", flush=True)
D.barrier()
if rank == 0:
    time.sleep(0.8)
    leak = leak_keys()
    print(f"KEYS {len(leak)} {leak[:8]}", flush=True)
mgr.stop()
print("WORKER_DONE", flush=True)
"""

_REFERENCE = _TRAIN_SETUP + r"""
import os, sys
steps = int(os.environ["GATE_STEPS"])
losses = [sgd_step() for _ in range(steps)]
print("LOSSES", json.dumps(losses))
print(f"DIGEST {param_digest()}", flush=True)
"""


def scenario_elastic_bitwise(verbose: bool = True) -> bool:
    port = _free_port()
    with tempfile.TemporaryDirectory(prefix="dist_gate_elastic_") as d:
        ref_script = os.path.join(d, "ref.py")
        with open(ref_script, "w") as f:
            f.write(_REFERENCE)
        env = _worker_env(0, 1, port, GATE_STEPS=STEPS)
        env.pop("PADDLE_MASTER")
        env.pop("PADDLE_TRAINERS_NUM")
        ref = subprocess.run([sys.executable, "-u", ref_script], env=env,
                             capture_output=True, text=True, timeout=600,
                             cwd=_REPO_ROOT)
        if ref.returncode != 0:
            print(f"dist_fault_gate: FAIL [elastic] reference run rc="
                  f"{ref.returncode}\n{ref.stdout[-800:]}{ref.stderr[-800:]}")
            return False
        ref_losses = json.loads(
            ref.stdout.split("LOSSES ")[1].splitlines()[0])
        ref_digest = ref.stdout.split("DIGEST ")[1].split()[0]

        worker = os.path.join(d, "worker.py")
        with open(worker, "w") as f:
            f.write(_ELASTIC_WORKER)
        log_dir = os.path.join(d, "logs")
        env = _worker_env(0, 2, port, GATE_CKDIR=d, GATE_STEPS=STEPS,
                          GATE_KILL_AT=KILL_AT)
        for k in ("PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM",
                  "PADDLE_MASTER"):
            env.pop(k)  # the launcher owns the per-rank env
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--elastic_level", "1",
             "--max_restart", "2", "--master", f"127.0.0.1:{port}",
             "--log_dir", log_dir, worker],
            env=env, capture_output=True, text=True, timeout=900,
            cwd=_REPO_ROOT)
        logs = {}
        if os.path.isdir(log_dir):
            for name in sorted(os.listdir(log_dir)):
                with open(os.path.join(log_dir, name)) as f:
                    logs[name] = f.read()
        if proc.returncode != 0:
            print(f"dist_fault_gate: FAIL [elastic] launcher rc="
                  f"{proc.returncode}\n{proc.stderr[-1500:]}")
            for name, text in logs.items():
                print(f"--- {name} ---\n{text[-800:]}")
            return False
        if "elastic restart 1/2" not in proc.stderr:
            print("dist_fault_gate: FAIL [elastic] the injected death never "
                  f"triggered a relaunch\n{proc.stderr[-800:]}")
            return False
        ok = True
        for name, text in logs.items():
            rank = int(name.rsplit(".", 1)[1])
            if "WORKER_DONE" not in text:
                print(f"dist_fault_gate: FAIL [elastic] rank {rank} did not "
                      f"finish\n{text[-1500:]}")
                ok = False
                continue
            losses = json.loads(
                text.split("LOSSES ")[-1].splitlines()[0])
            digest = text.split("DIGEST ")[-1].split()[0]
            # the relaunched rank resumes from the newest checkpoint ALL
            # members hold — at most KILL_AT (possibly earlier if its
            # async step save had not committed when it died), so its
            # results are a None prefix followed by EXACTLY the
            # reference losses; the survivor has every step for real
            nones = [i for i, v in enumerate(losses) if v is None]
            prefix_ok = nones == list(range(len(nones))) \
                and len(nones) <= (KILL_AT if rank == 1 else 0)
            if (len(losses) != len(ref_losses) or not prefix_ok
                    or losses[len(nones):] != ref_losses[len(nones):]):
                print(f"dist_fault_gate: FAIL [elastic] rank {rank} losses "
                      f"diverged from the uninterrupted run\n got {losses}\n"
                      f"ref {ref_losses}")
                ok = False
            if digest != ref_digest:
                print(f"dist_fault_gate: FAIL [elastic] rank {rank} final "
                      f"params diverged (digest {digest[:12]} != "
                      f"{ref_digest[:12]})")
                ok = False
        if ok and "KEYS 0" not in logs.get("workerlog.0", ""):
            print("dist_fault_gate: FAIL [elastic] store keys leaked after "
                  "drain\n" + logs.get("workerlog.0", "")[-800:])
            ok = False
        if ok and verbose:
            rec = logs["workerlog.0"].split("RECOVERIES ")[-1].split()[0]
            print("dist_fault_gate: kill->restart->bitwise-resume OK "
                  f"(rank-0 recoveries={rec}, losses + param digest equal "
                  "to the uninterrupted run on both ranks)")
        return ok


# ---------------------------------------------------------------------------

def gate() -> int:
    t0 = time.monotonic()
    ok = True
    ok &= scenario_kill_rank()
    ok &= scenario_restart_stale_keys()
    ok &= scenario_store_outage()
    ok &= scenario_elastic_bitwise()
    if not ok:
        return 1
    print(f"dist_fault_gate: OK (kill-a-rank, restart-stale-keys, "
          f"store-outage storm, elastic bitwise resume — typed errors, "
          f"generation isolation, exact key accounting; "
          f"{time.monotonic() - t0:.0f}s)")
    return 0


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    return gate()


if __name__ == "__main__":
    sys.exit(main())
