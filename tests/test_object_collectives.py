"""Object collectives + batch p2p + stream namespace (reference:
distributed/communication/{all_gather,batch_isend_irecv,stream}).
Single-process semantics here; the store transport is the same code
path the cross-host p2p send/recv tests exercise."""
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.distributed as D

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_object_collectives_single_process():
    objs = []
    D.all_gather_object(objs, {"a": 1})
    assert objs == [{"a": 1}]
    ol = [{"x": 2}]
    D.broadcast_object_list(ol, src=0)
    assert ol == [{"x": 2}]
    out = []
    D.scatter_object_list(out, [[1, 2]], src=0)
    assert out == [[1, 2]]


def test_gather_wait_batch_p2p_stream():
    t = pt.to_tensor(np.ones((2,), np.float32))
    assert D.wait(t) is t
    gl = []
    D.gather(t, gl, dst=0)
    # replicated fallback: one copy per rank of the default group
    assert len(gl) >= 1
    for g in gl:
        np.testing.assert_allclose(g.numpy(), [1, 1])

    dst = pt.to_tensor(np.zeros((2,), np.float32))
    ops_ = [D.P2POp(D.isend, t, 0), D.P2POp(D.irecv, dst, 0)]
    D.batch_isend_irecv(ops_)
    np.testing.assert_allclose(dst.numpy(), [1, 1])
    with pytest.raises(ValueError):
        D.P2POp(print, t, 0)

    from paddle_tpu.distributed import stream as S

    S.all_reduce(t)                      # sync delegation
    np.testing.assert_allclose(t.numpy(), [1, 1])
    # reshard is re-exported at the distributed level
    assert hasattr(D, "reshard")


_CHILD = r"""
import os, sys
os.environ["PADDLE_TRAINER_ID"] = "1"
os.environ["PADDLE_TRAINERS_NUM"] = "2"
os.environ["PADDLE_MASTER"] = "127.0.0.1:%PORT%"
os.environ["PADDLE_TPU_NO_JAX_DIST"] = "1"
import paddle_tpu.distributed as D
from paddle_tpu.distributed import env as E
E.init_parallel_env()
for i in range(5):
    objs = []
    D.all_gather_object(objs, {"rank": 1, "round": i})
    assert objs == [{"rank": 0, "round": i},
                    {"rank": 1, "round": i}], objs
ol = [None]
D.broadcast_object_list(ol, src=0)
assert ol == ["from0"], ol
print("CHILD_DONE")
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def test_object_collectives_cross_process(tmp_path):
    from paddle_tpu.distributed import env as E

    port = _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO_ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                        if p])
    env["JAX_PLATFORMS"] = "cpu"
    script = tmp_path / "child.py"
    script.write_text(_CHILD.replace("%PORT%", str(port)))
    proc = subprocess.Popen([sys.executable, str(script)], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    saved = (E._parallel_env, E._store, E._initialized)
    try:
        os.environ["PADDLE_TRAINER_ID"] = "0"
        os.environ["PADDLE_TRAINERS_NUM"] = "2"
        os.environ["PADDLE_MASTER"] = f"127.0.0.1:{port}"
        os.environ["PADDLE_TPU_NO_JAX_DIST"] = "1"
        E._parallel_env = None
        E._store = None
        E._initialized = False
        E.init_parallel_env()
        for i in range(5):
            objs = []
            D.all_gather_object(objs, {"rank": 0, "round": i})
            assert objs == [{"rank": 0, "round": i},
                            {"rank": 1, "round": i}], objs
        ol = ["from0"]
        D.broadcast_object_list(ol, src=0)
        assert ol == ["from0"]
        out, _ = proc.communicate(timeout=120)
        assert proc.returncode == 0, out[-1500:]
        assert "CHILD_DONE" in out
        # leak regression (PR-11 satellite): N collective rounds used to
        # leave one __barrier__/obj/.../done counter per round on the
        # rank-0 store forever; now payload AND barrier keys all sweep
        import time as _time

        _time.sleep(0.5)  # the child's barrier departures finish sweeps
        store = E.get_store()
        leaked = [k for k in store.keys()
                  if "/obj/" in k or k.startswith("__barrier__/g")]
        assert leaked == [], f"store grew {len(leaked)} keys: {leaked[:8]}"
    finally:
        proc.kill()
        for k in ("PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM",
                  "PADDLE_MASTER", "PADDLE_TPU_NO_JAX_DIST"):
            os.environ.pop(k, None)
        E._parallel_env, E._store, E._initialized = saved
