"""Elastic manager + launcher restart (reference:
fleet/elastic/manager.py:124 heartbeat/TTL membership; launcher
max_restart relaunch)."""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from paddle_tpu.core.native.tcp_store import TCPStore
from paddle_tpu.distributed.fleet.elastic import ElasticManager, ElasticStatus

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_membership_and_failure_detection():
    store = TCPStore(host="127.0.0.1", port=0, is_master=True, world_size=2)
    changes = []
    m0 = ElasticManager(store, rank=0, nnodes=2, ttl=1.0, interval=0.2,
                        on_change=lambda alive: changes.append(alive))
    m1 = ElasticManager(store, rank=1, nnodes=2, ttl=1.0, interval=0.2)
    m0.start()
    m1.start()
    time.sleep(0.6)
    assert sorted(m0.alive_nodes()) == [0, 1]
    assert m0.health() == ElasticStatus.COMPLETED
    # node 1 dies (heartbeat stops); TTL expires -> membership change fires.
    # Wait on the CALLBACK (the notification contract), not wall-clock: the
    # detector that observes the change must fire on_change before any
    # caller can see the shrunken membership.
    m1.stop()
    deadline = time.time() + 10
    while time.time() < deadline and not any(a == [0] for a in changes):
        time.sleep(0.2)
    assert any(alive == [0] for alive in changes)
    assert m0.alive_nodes() == [0]
    assert m0.health() in (ElasticStatus.RESTART, ElasticStatus.HOLD)
    m0.stop()


def test_restart_same_rank_mid_ttl_no_spurious_change():
    """A rank whose process restarts and re-registers under the SAME rank
    id BEFORE its TTL expires must never be reported dead: the beat
    counter keeps moving (the new incarnation's add continues the old
    counter), so membership stays stable and on_change never fires."""
    store = TCPStore(host="127.0.0.1", port=0, is_master=True, world_size=2)
    changes = []
    m0 = ElasticManager(store, rank=0, nnodes=2, ttl=0.8, interval=0.1,
                        on_change=lambda alive: changes.append(list(alive)))
    m1 = ElasticManager(store, rank=1, nnodes=2, ttl=0.8, interval=0.1)
    m0.start()
    m1.start()
    time.sleep(0.3)
    assert sorted(m0.alive_nodes()) == [0, 1]
    # incarnation A dies...
    m1.stop()
    # ...and incarnation B re-registers under rank 1 well inside the TTL
    time.sleep(0.2)
    m1b = ElasticManager(store, rank=1, nnodes=2, ttl=0.8, interval=0.1)
    m1b.start()
    # observe for ~2x TTL: membership must stay [0, 1] throughout
    deadline = time.time() + 1.6
    while time.time() < deadline:
        assert sorted(m0.alive_nodes()) == [0, 1]
        time.sleep(0.1)
    assert changes == [], f"spurious membership change(s): {changes}"
    m0.stop()
    m1b.stop()


def test_deliver_retries_after_failing_chained_callback():
    """chain_on_change keeps the delivery contract: when the chained
    callback raises, the notification is NOT swallowed — the next
    detection re-fires it (and the failure never propagates into the
    alive_nodes() caller)."""
    store = TCPStore(host="127.0.0.1", port=0, is_master=True, world_size=2)
    order = []

    def first(alive):
        order.append(("first", list(alive)))

    boom = [True]

    def chained(alive):
        if boom[0]:
            boom[0] = False
            raise RuntimeError("flaky downstream")
        order.append(("chained", list(alive)))

    m0 = ElasticManager(store, rank=0, nnodes=2, ttl=0.5, interval=0.1,
                        on_change=first)
    m0.chain_on_change(chained)
    m1 = ElasticManager(store, rank=1, nnodes=2, ttl=0.5, interval=0.1)
    m0.start()
    m1.start()
    time.sleep(0.3)
    m0.alive_nodes()  # records [0, 1] silently (first computation)
    m1.stop()         # rank 1 dies -> change to [0]
    deadline = time.time() + 12
    while time.time() < deadline and ("chained", [0]) not in order:
        m0.alive_nodes()  # must never raise despite the failing callback
        time.sleep(0.1)
    assert ("chained", [0]) in order, order
    # the retry re-ran the WHOLE chain in order: first fired (at least)
    # twice — the failed delivery and the successful retry
    firsts = [o for o in order if o == ("first", [0])]
    assert len(firsts) >= 2, order
    assert order.index(("first", [0])) < order.index(("chained", [0]))
    m0.stop()


def test_wait_returns_false_exactly_at_monotonic_deadline(monkeypatch):
    """wait()'s deadline check is strict (`now < deadline`): a clock that
    lands EXACTLY on the deadline returns False instead of sneaking one
    more membership poll in."""
    import paddle_tpu.distributed.fleet.elastic as elastic_mod

    store = TCPStore(host="127.0.0.1", port=0, is_master=True, world_size=2)
    m = ElasticManager(store, rank=0, nnodes=2, ttl=1.0, interval=0.2)
    polled = []
    m.alive_nodes = lambda: polled.append(1) or [0]  # would be < min=2

    class FakeTime:
        def __init__(self, base):
            self._t = base
            self._calls = 0

        def monotonic(self):
            self._calls += 1
            # call 1 computes the deadline (base + timeout); call 2 lands
            # exactly ON it
            return self._t if self._calls == 1 else self._t + 5.0

        @staticmethod
        def sleep(_s):
            raise AssertionError("wait() slept past its deadline")

    monkeypatch.setattr(elastic_mod, "time", FakeTime(1000.0))
    assert m.wait(timeout=5.0) is False
    assert polled == [], "alive_nodes polled at/past the deadline"


def test_launcher_elastic_restart(tmp_path):
    """A worker that crashes once is relaunched and the job succeeds."""
    script = tmp_path / "flaky.py"
    marker = tmp_path / "crashed_once"
    script.write_text(
        "import os, sys\n"
        f"m = {str(repr(str(marker)))}\n"
        "if not os.path.exists(m):\n"
        "    open(m, 'w').close()\n"
        "    sys.exit(3)\n"
        "print('RECOVERED_OK')\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO_ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                        if p])
    log_dir = str(tmp_path / "logs")
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--elastic_level", "1",
         "--max_restart", "2", "--log_dir", log_dir, str(script)],
        cwd=_REPO_ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-1500:]
    logs = "".join(
        open(os.path.join(log_dir, f)).read() for f in os.listdir(log_dir))
    assert "RECOVERED_OK" in logs
    assert "elastic restart 1/2" in proc.stderr


def test_launcher_refuses_two_workers_on_a_tpu_host(monkeypatch):
    """A chip belongs to one process: on a TPU host a second local worker
    is an error at launch, unless the workers are pinned off the TPU."""
    from paddle_tpu.distributed.launch import main as launch

    monkeypatch.setattr(launch, "_local_tpu_chips", lambda: 4)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit, match="belongs to one process"):
        launch._check_one_worker_per_tpu_host(2)
    launch._check_one_worker_per_tpu_host(1)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    launch._check_one_worker_per_tpu_host(2)
    monkeypatch.setattr(launch, "_local_tpu_chips", lambda: 0)
    monkeypatch.delenv("JAX_PLATFORMS")
    launch._check_one_worker_per_tpu_host(2)


def test_launcher_fail_fast_without_elastic(tmp_path):
    script = tmp_path / "dies.py"
    script.write_text("import sys; sys.exit(5)\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO_ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                        if p])
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", str(script)],
        cwd=_REPO_ROOT, env=env, capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 5
