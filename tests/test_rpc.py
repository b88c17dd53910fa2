"""distributed.rpc over the native TCPStore (reference:
python/paddle/distributed/rpc/rpc.py; transport here is the job's C++
TCPStore control plane instead of a second brpc stack)."""
import operator
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from paddle_tpu.distributed import rpc

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture
def fresh_rpc():
    yield
    rpc.shutdown()


def test_self_rpc_sync_async_and_exception(fresh_rpc):
    rpc._state.store = None
    rpc.init_rpc("worker0", rank=0, world_size=1,
                 master_endpoint=f"127.0.0.1:{_free_port()}")
    try:
        assert rpc.rpc_sync("worker0", operator.add, args=(2, 3)) == 5
        fut = rpc.rpc_async("worker0", operator.mul, args=(4, 5))
        assert fut.result(timeout=30) == 20
        with pytest.raises(ZeroDivisionError):
            rpc.rpc_sync("worker0", operator.truediv, args=(1, 0))
        info = rpc.get_worker_info("worker0")
        assert info.rank == 0 and info.name == "worker0"
        assert len(rpc.get_all_worker_infos()) == 1
    finally:
        rpc.shutdown()


_CHILD = r"""
import sys, time
from paddle_tpu.distributed import rpc

rpc.init_rpc("worker1", rank=1, world_size=2,
             master_endpoint=f"127.0.0.1:{sys.argv[1]}")
# serve until the shutdown barrier completes
rpc.shutdown()
print("CHILD_DONE")
"""


def test_cross_process_rpc(tmp_path, fresh_rpc):
    port = _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO_ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                        if p])
    env["JAX_PLATFORMS"] = "cpu"
    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    proc = subprocess.Popen([sys.executable, str(script), str(port)],
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    try:
        rpc._state.store = None
        rpc.init_rpc("worker0", rank=0, world_size=2,
                     master_endpoint=f"127.0.0.1:{port}")
        assert rpc.rpc_sync("worker1", operator.add, args=(20, 22),
                            timeout=60) == 42
        infos = rpc.get_all_worker_infos()
        assert {i.name for i in infos} == {"worker0", "worker1"}
    finally:
        rpc.shutdown()
    out, _ = proc.communicate(timeout=60)
    assert proc.returncode == 0, out[-1500:]
    assert "CHILD_DONE" in out
