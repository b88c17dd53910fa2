import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import Tensor


def test_to_tensor_basics():
    t = paddle_tpu.to_tensor([[1.0, 2.0], [3.0, 4.0]])
    assert t.shape == [2, 2]
    assert t.dtype == paddle_tpu.float32
    np.testing.assert_array_equal(t.numpy(), [[1, 2], [3, 4]])


def test_int_default_dtype():
    assert paddle_tpu.to_tensor(3).dtype == paddle_tpu.int64
    assert paddle_tpu.to_tensor(3.0).dtype == paddle_tpu.float32
    assert paddle_tpu.to_tensor(True).dtype.name == "bool"


def test_numpy_dtype_preserved():
    a = np.arange(4, dtype=np.int32)
    assert paddle_tpu.to_tensor(a).dtype == paddle_tpu.int32


def test_astype_cast():
    t = paddle_tpu.to_tensor([1.5, 2.5])
    assert t.astype("int64").dtype == paddle_tpu.int64
    assert t.astype(paddle_tpu.bfloat16).dtype == paddle_tpu.bfloat16


def test_operators():
    x = paddle_tpu.to_tensor([1.0, 2.0, 3.0])
    y = paddle_tpu.to_tensor([4.0, 5.0, 6.0])
    np.testing.assert_allclose((x + y).numpy(), [5, 7, 9])
    np.testing.assert_allclose((y - x).numpy(), [3, 3, 3])
    np.testing.assert_allclose((x * y).numpy(), [4, 10, 18])
    np.testing.assert_allclose((y / x).numpy(), [4, 2.5, 2])
    np.testing.assert_allclose((x**2).numpy(), [1, 4, 9])
    np.testing.assert_allclose((2.0 + x).numpy(), [3, 4, 5])
    np.testing.assert_allclose((-x).numpy(), [-1, -2, -3])
    assert bool((x < y).all())
    assert bool((x == x).all())


def test_matmul_operator():
    a = paddle_tpu.to_tensor(np.eye(3, dtype=np.float32))
    b = paddle_tpu.to_tensor(np.arange(9, dtype=np.float32).reshape(3, 3))
    np.testing.assert_allclose((a @ b).numpy(), b.numpy())


def test_indexing():
    t = paddle_tpu.to_tensor(np.arange(12).reshape(3, 4))
    np.testing.assert_array_equal(t[0].numpy(), [0, 1, 2, 3])
    np.testing.assert_array_equal(t[:, 1].numpy(), [1, 5, 9])
    np.testing.assert_array_equal(t[1:, 2:].numpy(), [[6, 7], [10, 11]])


def test_setitem():
    t = paddle_tpu.to_tensor(np.zeros((3, 3), np.float32))
    t[1] = 5.0
    assert t.numpy()[1].tolist() == [5, 5, 5]


def test_item_and_len():
    t = paddle_tpu.to_tensor([[7.0]])
    assert t.item() == 7.0
    assert len(paddle_tpu.to_tensor([1, 2, 3])) == 3


def test_detach_clone():
    x = paddle_tpu.to_tensor([1.0], stop_gradient=False)
    d = x.detach()
    assert d.stop_gradient
    c = x.clone()
    assert not c.stop_gradient


def test_parameter():
    p = paddle_tpu.Parameter(paddle_tpu.to_tensor([1.0, 2.0])._value)
    assert not p.stop_gradient
    assert p.trainable


def test_creation_ops():
    assert paddle_tpu.zeros([2, 3]).shape == [2, 3]
    assert paddle_tpu.ones([2], "int32").dtype == paddle_tpu.int32
    np.testing.assert_array_equal(paddle_tpu.arange(5).numpy(), np.arange(5))
    assert paddle_tpu.full([2, 2], 7.0).numpy().tolist() == [[7, 7], [7, 7]]
    np.testing.assert_allclose(paddle_tpu.eye(3).numpy(), np.eye(3))
    assert paddle_tpu.linspace(0, 1, 5).shape == [5]


def test_rand_ops_shapes():
    paddle_tpu.seed(0)
    assert paddle_tpu.rand([4, 4]).shape == [4, 4]
    assert paddle_tpu.randn([3]).shape == [3]
    r = paddle_tpu.randint(0, 10, [100])
    assert int(r.max()) < 10 and int(r.min()) >= 0
    p = paddle_tpu.randperm(16)
    assert sorted(p.numpy().tolist()) == list(range(16))


def test_seed_reproducible():
    paddle_tpu.seed(42)
    a = paddle_tpu.randn([8]).numpy()
    paddle_tpu.seed(42)
    b = paddle_tpu.randn([8]).numpy()
    np.testing.assert_array_equal(a, b)


class TestDataLoaderWorkers:
    """num_workers>0 runs real forked worker processes (reference
    dataloader_iter.py _DataLoaderIterMultiProcess)."""

    def test_multiprocess_dataloader_order_and_values(self):
        import numpy as np
        import paddle_tpu as pt
        from paddle_tpu.io import DataLoader, Dataset

        class Squares(Dataset):
            def __len__(self):
                return 23

            def __getitem__(self, i):
                return np.asarray([i * i], dtype=np.float32), np.int64(i)

        dl = DataLoader(Squares(), batch_size=4, num_workers=2, shuffle=False)
        xs, ys = [], []
        for bx, by in dl:
            xs.append(bx.numpy())
            ys.append(by.numpy())
        got = np.concatenate([y.reshape(-1) for y in ys])
        np.testing.assert_array_equal(got, np.arange(23))
        np.testing.assert_allclose(
            np.concatenate([x.reshape(-1) for x in xs]), np.arange(23) ** 2)

    def test_worker_exception_propagates(self):
        import numpy as np
        import pytest
        from paddle_tpu.io import DataLoader, Dataset

        class Bad(Dataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                if i == 5:
                    raise ValueError("boom-5")
                return np.zeros(2, np.float32)

        dl = DataLoader(Bad(), batch_size=2, num_workers=2)
        with pytest.raises(RuntimeError, match="worker failed"):
            list(dl)

    def test_worker_device_array_rejected(self):
        """Workers are forked from a process whose JAX runtime is live:
        they stay on numpy, and a sample that holds a device array is an
        error, not a fork-unsafe touch of the device."""
        import numpy as np
        import pytest
        import paddle_tpu as pt
        from paddle_tpu.io import DataLoader, Dataset

        class OnDevice(Dataset):
            def __init__(self):
                self.sample = pt.to_tensor(np.zeros(2, np.float32))

            def __len__(self):
                return 4

            def __getitem__(self, i):
                return self.sample      # made in the parent, before the fork

        dl = DataLoader(OnDevice(), batch_size=2, num_workers=2)
        with pytest.raises(RuntimeError, match="device array"):
            list(dl)

    def test_worker_init_fn_called(self):
        import numpy as np
        from paddle_tpu.io import DataLoader, Dataset

        class DS(Dataset):
            def __len__(self):
                return 4

            def __getitem__(self, i):
                import os
                return np.asarray([float(os.environ.get("_PT_WID", -1))],
                                  np.float32)

        def init(wid):
            import os
            os.environ["_PT_WID"] = str(wid)

        dl = DataLoader(DS(), batch_size=2, num_workers=2, worker_init_fn=init)
        vals = np.concatenate([b.numpy().reshape(-1) for b in dl])
        assert set(vals.tolist()) <= {0.0, 1.0}
        assert len(vals) == 4


class TestNanInfChecking:
    """FLAGS_check_nan_inf (reference eager/nan_inf_utils.cc): strict mode
    aborts per op; deferred mode accumulates device-side and reports on a
    single sync (no per-op host round trips)."""

    def test_strict_mode_raises(self):
        import numpy as np
        import pytest
        import paddle_tpu as pt

        pt.set_flags({"FLAGS_check_nan_inf": True,
                      "FLAGS_check_nan_inf_level": 0})
        try:
            x = pt.to_tensor(np.array([1.0, 0.0], np.float32))
            with pytest.raises(FloatingPointError):
                _ = pt.ops.log(x * 0.0 - 1.0)  # log(-1) = nan
        finally:
            pt.set_flags({"FLAGS_check_nan_inf": False})

    def test_deferred_mode_reports_once(self):
        import numpy as np
        import paddle_tpu as pt
        from paddle_tpu.amp.debugging import finite_check_report

        pt.set_flags({"FLAGS_check_nan_inf": True,
                      "FLAGS_check_nan_inf_level": 1})
        try:
            x = pt.to_tensor(np.array([1.0, 2.0], np.float32))
            _ = pt.ops.log(x)       # fine
            assert finite_check_report() is True
            _ = pt.ops.log(-x)      # nan, but NO exception mid-loop
            _ = pt.ops.sqrt(x)
            assert finite_check_report() is False
            # state reset after report
            assert finite_check_report() is True
        finally:
            pt.set_flags({"FLAGS_check_nan_inf": False})
