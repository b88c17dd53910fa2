"""The convolution's kept inputs written a run at a time, in place
(``ops/pallas_kernels/kept_rows.py``, docs/serving.md "A third kind of state
in the slot"), under the Pallas interpreter against the models' XLA form
(``models/decoder_ops.kept_inputs`` as the CPU runs it), at both row shapes
the cells keep: Solar-Open2's ``(576, 128)`` (three parts of 192 sublane rows)
and Phi-4-mini-flash's ``(120, 128)`` (three of 40).

- the launch alone against a plain loop over the runs: each run's ``run_dst``
  row holds its last three inputs, its old row comes back (zeros where it
  starts a sequence), every other pool row is bitwise what it was;
- the two forms of ``kept_inputs`` on the same step: decode runs of one row,
  runs of 2, 3 and more rows, fresh runs, padding rows, a layer past the
  first: the same earlier inputs on every real row, the same row at every
  run's ``run_dst``, and the launch leaves every other row (the layer's row
  0 included, which the XLA form uses as a sink) bitwise as it was;
- where the launch is taken: never off a TPU; on one, a row of whole tiles;
  another row notes the fallback.

``tests/test_pool_in_place.py`` compiles the launch for the described v5e at
both cells' geometry and holds the compiled steps to it."""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import decoder_ops
from paddle_tpu.ops.pallas_kernels import kept_rows as kr
from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

ROWS = {"solar": 576, "phi4": 120}

# runs' lengths in flat-token order; None: a padding row after them
_STEPS = {
    "decode_rows": [1] * 11,
    "mixed": [1, 3, 1, 2, 7, 1, 1, 5, 1, 1, 2],
    "one_long_run": [40],
    "short_runs_then_padding": [2, 1, 3, 1, None, None, None],
}


def _step(case, rows, slots=12, layers=2, a_dtype=jnp.bfloat16, seed=0):
    """A step's pool (``layers`` layers of ``2 slots + 1`` rows, random
    bits), its inputs ``a`` and its run list (slots drawn at random, every
    other run fresh)."""
    rng = np.random.default_rng(seed)
    counts = [c for c in _STEPS[case] if c is not None]
    padding = len(_STEPS[case]) - len(counts)
    n = len(counts)
    width = rows // 3 * 128
    t = sum(counts) + padding
    first = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
    slot = rng.permutation(slots)[:n]
    flip = rng.integers(0, 2, n)
    src = (1 + 2 * slot + flip).astype(np.int32)
    fresh = (np.arange(n) % 2 == 1).astype(np.int32)

    def pad(x):
        return jnp.asarray(np.concatenate([x, np.zeros(slots - n, np.int32)]), jnp.int32)

    runs = (pad(first), pad(np.asarray(counts, np.int32)), pad(src),
            pad((1 + 2 * slot + 1 - flip).astype(np.int32)), pad(fresh),
            jnp.asarray([n], jnp.int32))
    pool = jnp.asarray(rng.normal(size=(layers * (2 * slots + 1), rows, 128)),
                       jnp.bfloat16)
    a = jnp.asarray(rng.normal(size=(t, width)), a_dtype)
    pos = np.zeros(t, np.int32)
    for r in range(n):      # a fresh run starts at position 0, the others later
        start = 0 if fresh[r] else 100 + r
        pos[first[r]:first[r] + counts[r]] = start + np.arange(counts[r])
    return pool, a, runs, jnp.asarray(pos), counts


def _bits(x):
    return np.asarray(x).view(np.uint16 if x.dtype == jnp.bfloat16 else np.uint32)


@pytest.mark.parametrize("shape", list(ROWS))
@pytest.mark.parametrize("case", list(_STEPS))
def test_the_launch_keeps_each_runs_last_three_inputs(shape, case):
    rows = ROWS[shape]
    pool, a, runs, _, counts = _step(case, rows, seed=rows)
    first, _, src, dst, fresh, _ = (np.asarray(x) for x in runs)
    new_pool, old = kr.kept_rows(pool, a, runs, interpret=True)
    width = rows // 3 * 128
    want = np.asarray(pool.astype(jnp.float32)).copy()
    for r, count in enumerate(counts):
        held = np.zeros((3, width), np.float32) if fresh[r] else want[src[r]].reshape(3, width)
        np.testing.assert_array_equal(np.asarray(old[3 * r:3 * r + 3].astype(jnp.float32)), held)
        seq = np.concatenate([held, np.asarray(a[first[r]:first[r] + count].astype(jnp.float32))])
        want[dst[r]] = seq[-3:].reshape(rows, 128)
    np.testing.assert_array_equal(_bits(new_pool), _bits(jnp.asarray(want, jnp.bfloat16)))
    assert old.shape == (3 * 16, width) and old.dtype == pool.dtype


def _both_forms(case, rows, monkeypatch, layer=1, a_dtype=jnp.bfloat16):
    """``kept_inputs`` as the CPU takes it, and with the launch (interpreted)."""
    pool, a, runs, pos, counts = _step(case, rows, a_dtype=a_dtype, seed=7 + rows)
    step_rows = decoder_ops.rows_of_step(pos, runs)
    base = layer * (pool.shape[0] // 2)
    scopes = ("test.conv", "test.state_write")
    assert not kr.kept_rows_runs(pool.shape[1:], a.dtype, pool.dtype)     # the CPU
    xla = decoder_ops.kept_inputs(a, pool, base, step_rows, runs, scopes)
    with monkeypatch.context() as patch:
        patch.setattr(kr, "kept_rows_runs", lambda *_: True)
        patch.setattr(kr, "kept_rows", functools.partial(kr.kept_rows, interpret=True))
        launch = decoder_ops.kept_inputs(a, pool, base, step_rows, runs, scopes)
    return pool, runs, base, counts, xla, launch


@pytest.mark.parametrize("shape", list(ROWS))
@pytest.mark.parametrize("case", list(_STEPS))
def test_the_launch_and_the_xla_form_give_the_same_inputs_and_rows(shape, case, monkeypatch):
    pool, runs, base, counts, (prev_x, pool_x), (prev_l, pool_l) = _both_forms(
        case, ROWS[shape], monkeypatch)
    real = sum(counts)
    for px, pl_ in zip(prev_x, prev_l):
        assert px.dtype == pl_.dtype
        np.testing.assert_array_equal(_bits(px[:real]), _bits(pl_[:real]))
    dst = np.asarray(runs[3])[:len(counts)] + base
    np.testing.assert_array_equal(_bits(pool_l[dst]), _bits(pool_x[dst]))
    others = np.setdiff1d(np.arange(pool.shape[0]), dst)
    assert base in others                                   # the layer's row 0
    np.testing.assert_array_equal(_bits(pool_l[others]), _bits(pool[others]))


def test_float32_inputs_are_kept_as_the_xla_form_rounds_them(monkeypatch):
    """Inputs wider than the pool: both forms round each kept input to the
    pool's bfloat16 the same way, and read the same float32 earlier inputs."""
    pool, runs, base, counts, (prev_x, pool_x), (prev_l, pool_l) = _both_forms(
        "mixed", ROWS["phi4"], monkeypatch, layer=0, a_dtype=jnp.float32)
    real = sum(counts)
    for px, pl_ in zip(prev_x, prev_l):
        assert px.dtype == pl_.dtype == jnp.float32
        np.testing.assert_array_equal(_bits(px[:real]), _bits(pl_[:real]))
    dst = np.asarray(runs[3])[:len(counts)] + base
    np.testing.assert_array_equal(_bits(pool_l[dst]), _bits(pool_x[dst]))


def test_the_launch_is_taken_on_a_tpu_for_a_row_of_whole_tiles(monkeypatch, capsys):
    from paddle_tpu.analysis import codes

    bf16 = jnp.bfloat16
    assert not kr.kept_rows_runs((576, 128), bf16, bf16)                 # not a TPU
    monkeypatch.setattr(ra, "_on_tpu", lambda: True)
    monkeypatch.setattr(codes, "_SEEN_FALLBACKS", set())
    assert kr.kept_rows_runs((576, 128), bf16, bf16)
    assert kr.kept_rows_runs((120, 128), jnp.float32, bf16)
    assert capsys.readouterr().err == ""
    # the tiny models' rows (one slab of 576 values; parts of 4 sublane rows)
    # take the XLA form and say so
    assert not kr.kept_rows_runs((1, 576), bf16, bf16)
    assert not kr.kept_rows_runs((12, 128), bf16, bf16)
    assert not kr.kept_rows_runs((576, 128), jnp.float16, bf16)
    err = capsys.readouterr().err
    assert err.count("GL002") == 3 and "kept_rows" in err
    with pytest.raises(ValueError, match="kept_rows"):
        kr.kept_rows(jnp.zeros((3, 12, 128), bf16), jnp.zeros((8, 512), bf16),
                     tuple(jnp.zeros((1,), jnp.int32) for _ in range(6)))


def test_a_steps_tiles_are_its_rows():
    """``as_tiles`` puts row ``8 g + s``, lane chunk ``c`` at ``[g, 8 c + s]``
    and ``from_tiles`` undoes it."""
    x = jnp.arange(16 * 384, dtype=jnp.float32).reshape(16, 384)
    tiles = kr.as_tiles(x)
    assert tiles.shape == (2, 24, 128)
    np.testing.assert_array_equal(tiles[1, 8 * 2 + 3], x[8 + 3, 256:384])
    np.testing.assert_array_equal(kr.from_tiles(tiles), x)


def test_an_engine_whose_convolution_takes_the_launch_serves_the_same(monkeypatch):
    """The wired step: a delta-rule model whose kept row is whole tiles
    (8 linear heads of 128: ``(72, 128)``), bfloat16 throughout, served
    by two engines, one whose convolution takes the launch (interpreted; the
    choice is patched here, nothing threads an option through the engine):
    the same tokens, and every state row but the layers' sink rows the same
    bits."""
    import paddle_tpu as pt
    from paddle_tpu.models import SolarOpen2ForCausalLM, solar_open2_tiny
    from paddle_tpu.serving import ServingEngine

    pt.seed(3)
    model = SolarOpen2ForCausalLM(solar_open2_tiny(
        linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 128,
                            "num_heads": 8, "num_kv_heads": None},
        experts_held=(4, 4), dtype="bfloat16"))
    model.eval()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n) for n in (5, 33, 21)]

    def serve():
        eng = ServingEngine(model, num_slots=3, page_size=16, max_context=128,
                            prefill_token_budget=24, cache_dtype="bfloat16")
        try:
            reqs = [eng.submit(p, 6) for p in prompts]
            eng.run_until_idle()
            assert tuple(eng.cache.kda_conv.shape[2:]) == (72, 128)
            return ([list(r.tokens) for r in reqs],
                    [np.asarray(t._value) for t in (eng.cache.kda, eng.cache.kda_conv)])
        finally:
            eng.close()

    tokens_x, (kda_x, conv_x) = serve()
    with monkeypatch.context() as patch:
        patch.setattr(kr, "kept_rows_runs", lambda *_: True)
        patch.setattr(kr, "kept_rows", functools.partial(kr.kept_rows, interpret=True))
        tokens_l, (kda_l, conv_l) = serve()
    assert tokens_l == tokens_x
    np.testing.assert_array_equal(kda_l, kda_x)
    np.testing.assert_array_equal(_bits(conv_l[:, 1:]), _bits(conv_x[:, 1:]))
