"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes."""
import jax.numpy as jnp
import numpy as np
import pytest

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------- gaussian
@pytest.mark.parametrize("h,w,ksize,tile", [(128, 64, 7, 16), (128, 256, 31, 64),
                                            (256, 128, 15, 32)])
def test_gaussian_kernel(h, w, ksize, tile):
    from repro.kernels.gaussian import kernel as K, ref as R
    img = RNG.standard_normal((h, w)).astype(np.float32)
    pad = ksize // 2
    ip = jnp.asarray(np.pad(img, pad, mode="edge"))
    wts = jnp.asarray(R.gaussian_weights(ksize))
    ref = R.blur_rows_ref(ip, wts, 0, h)
    got = K.blur_rows(ip, wts, tile_h=tile, interpret=True)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_gaussian_range_consistency():
    from repro.kernels.gaussian import ops, ref as R
    img = RNG.standard_normal((256, 128)).astype(np.float32)
    ip, w = ops.prepare(img)
    ipj, wj = jnp.asarray(ip), jnp.asarray(w)
    full = R.blur_full_ref(jnp.asarray(img))
    parts = [ops.run_range(ipj, wj, i, 1) for i in range(ops.total_work(img))]
    np.testing.assert_allclose(jnp.concatenate(parts, 0), full,
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- binomial
@pytest.mark.parametrize("n,steps,tile", [(256, 64, 64), (512, 254, 128)])
def test_binomial_kernel(n, steps, tile):
    from repro.kernels.binomial import kernel as K, ops, ref as R
    s0, k0, ty = map(jnp.asarray, ops.make_inputs(n))
    ref = R.price_options(s0, k0, ty, steps=steps)
    got = K.price_options(s0, k0, ty, steps=steps, tile=tile, interpret=True)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-3)


def test_binomial_monotone_in_spot():
    """Option value increases with the spot price (sanity property)."""
    from repro.kernels.binomial import ref as R
    s0 = jnp.linspace(5.0, 50.0, 20)
    k0 = jnp.full((20,), 25.0)
    ty = jnp.full((20,), 2.0)
    v = R.price_options(s0, k0, ty)
    assert bool(jnp.all(jnp.diff(v) >= -1e-5))


# -------------------------------------------------------------- mandelbrot
# (width, height, max_iter, (row0, n_rows), (col0, n_cols), where); n_rows
# 0 is the whole height, n_cols 0 the whole width; ``where`` names what the
# window holds, checked on the reference.  Interpret mode runs
# on XLA's CPU backend, which contracts multiply-adds into FMAs as its
# fusions allow, so long orbits near the set's boundary round differently
# from the jnp path's: the windows stay small, and the chip compares the
# two paths at full size (chip_smoke.py)
@pytest.mark.parametrize("w,h,iters,rows,cols,where", [
    pytest.param(64, 64, 64, (0, 0), (0, 0), None, id="64-64-64"),
    pytest.param(128, 32, 200, (0, 0), (0, 0), None, id="128-32-200"),
    pytest.param(64, 64, 150, (0, 0), (0, 0), None, id="iters-not-chunked"),
    pytest.param(64, 64, 1, (0, 0), (0, 0), None, id="one-iter"),
    # the view's top-left corner: every pixel escapes in the first chunk
    pytest.param(512, 512, 200, (0, 8), (0, 128), "outside",
                 id="all-outside"),
    # around c = -0.2 + 0i, inside the main cardioid: runs to max_iter
    pytest.param(512, 512, 100, (248, 16), (288, 128), "inside",
                 id="all-inside"),
    # a ragged column window, as the mandelbrot2d ROI adapter asks for it
    pytest.param(256, 256, 200, (96, 16), (40, 200), None,
                 id="column-window"),
    # c = -2 stays on the radius (z = -2, 2, 2, ...) while every other
    # pixel of its tile escapes at once: it alone keeps the tile running
    pytest.param(6, 1, 200, (0, 8), (-127, 128), "radius",
                 id="on-the-radius"),
])
def test_mandelbrot_kernel(w, h, iters, rows, cols, where):
    from repro.kernels.mandelbrot import kernel as K, ref as R
    row0, n_rows = rows[0], rows[1] or h
    col0, n_cols = cols
    ref = np.asarray(R.escape_counts(row0, n_rows, w, h, iters, col0, n_cols))
    got = K.escape_counts(row0, n_rows, w, h, iters, col0=col0,
                          n_cols=n_cols, tile_h=8, interpret=True)
    assert (ref == np.asarray(got)).all()
    if where == "outside":
        assert ref.max() < K.CHUNK
    if where == "inside":
        assert (ref == iters).all()
    if where == "radius":
        assert ref[0, -1] == iters and (ref == iters).sum() == 1


def test_mandelbrot_interior_maxes_out():
    from repro.kernels.mandelbrot import ref as R
    # the set's interior (c ~ -0.1 + 0i is inside) never escapes
    cnt = R.escape_counts(30, 4, 64, 64, 50)   # middle rows
    assert int(cnt.max()) == 50


# ------------------------------------------------------------------ nbody
@pytest.mark.parametrize("n,tile_t,tile_s", [(256, 64, 128), (512, 128, 256)])
def test_nbody_kernel(n, tile_t, tile_s):
    from repro.kernels.nbody import kernel as K, ops, ref as R
    pm, vel = ops.make_inputs(n)
    ref = R.accelerations(jnp.asarray(pm), 0, tile_t)
    got = K.accelerations(jnp.asarray(pm[:tile_t]), jnp.asarray(pm),
                          tile_t=tile_t, tile_s=tile_s, interpret=True)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_nbody_momentum_conservation():
    """Equal masses: total acceleration ~ 0 (Newton's third law)."""
    from repro.kernels.nbody import ref as R
    pm, _ = __import__("repro.kernels.nbody.ops", fromlist=["make_inputs"]) \
        .make_inputs(128)
    pm[:, 3] = 1.0
    acc = R.accelerations(jnp.asarray(pm), 0, 128)
    total = np.asarray(jnp.sum(acc * pm[:, 3:4], axis=0))
    assert np.abs(total).max() < 1e-2


# ---------------------------------------------------------------- ray
def test_ray_scenes_differ_and_shade():
    from repro.kernels.ray import ref as R
    s1, s2 = R.make_scene(1), R.make_scene(2)
    img1 = R.render_rows(s1, 0, 64, 64, 64)
    img2 = R.render_rows(s2, 0, 64, 64, 64)
    assert img1.shape == (64, 64, 3)
    assert float(jnp.abs(img1 - img2).max()) > 0.1
    assert bool(jnp.isfinite(img1).all())
    assert float(img1.max()) <= 1.5 and float(img1.min()) >= 0.0


# ------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,S,H,KH,D,bq,bk,dtype", [
    (2, 128, 4, 4, 64, 64, 64, jnp.float32),
    (1, 256, 8, 2, 64, 128, 64, jnp.float32),
    (1, 256, 4, 1, 128, 64, 128, jnp.float32),
    (2, 128, 8, 4, 80, 128, 32, jnp.float32),
    (1, 128, 4, 2, 64, 64, 64, jnp.bfloat16),
])
def test_flash_attention_kernel(B, S, H, KH, D, bq, bk, dtype):
    from repro.kernels.flash_attention import kernel as K, ref as R
    q = jnp.asarray(RNG.standard_normal((B, S, H, D)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, S, KH, D)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, S, KH, D)), dtype)
    ref = R.attention_ref(q, k, v)
    got = K.flash_attention(q, k, v, bq=bq, bk=bk, interpret=True)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=atol)


def test_flash_matches_blocked_jnp_path():
    from repro.kernels.flash_attention import ops
    from repro.kernels.flash_attention import ref as R
    q = jnp.asarray(RNG.standard_normal((1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 256, 2, 64)), jnp.float32)
    blocked = ops.attention(q, k, v, chunk=64)
    np.testing.assert_allclose(blocked, R.attention_ref(q, k, v),
                               rtol=1e-4, atol=2e-5)


# ---------------------------------------------------------- mamba scan
@pytest.mark.parametrize("B,S,di,ds,chunk,tile_d", [
    (1, 64, 32, 8, 16, 32),
    (2, 128, 64, 16, 64, 32),
    (2, 96, 48, 16, 32, 48),
])
def test_mamba_scan_kernel(B, S, di, ds, chunk, tile_d):
    from repro.kernels.mamba_scan import kernel as K, ref as R
    a = jnp.asarray(RNG.uniform(0.5, 0.99, (B, S, di, ds)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((B, S, di, ds)) * 0.1, jnp.float32)
    C = jnp.asarray(RNG.standard_normal((B, S, ds)), jnp.float32)
    yr, hr = R.selective_scan_ref(a, b, C)
    yp, hp = K.selective_scan(a, b, C, chunk=chunk, tile_d=tile_d,
                              interpret=True)
    np.testing.assert_allclose(yp, yr, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(hp, hr, rtol=1e-4, atol=1e-5)


def test_mamba_chunked_jnp_matches_ref():
    from repro.kernels.mamba_scan import ops, ref as R
    a = jnp.asarray(RNG.uniform(0.5, 0.99, (2, 128, 32, 8)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((2, 128, 32, 8)) * 0.1, jnp.float32)
    C = jnp.asarray(RNG.standard_normal((2, 128, 8)), jnp.float32)
    y1, h1 = ops.selective_scan(a, b, C, chunk=32)
    y2, h2 = R.selective_scan_ref(a, b, C)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(h1, h2, rtol=1e-4, atol=1e-5)


# ----------------------------------------------------------- flash decode
@pytest.mark.parametrize("B,S,H,KH,D,bk,pos", [
    (2, 256, 8, 4, 64, 64, 255),
    (1, 512, 4, 1, 128, 128, 300),     # masked tail inside a block
    (2, 256, 8, 8, 64, 256, 17),       # most blocks skipped
    (1, 128, 16, 2, 64, 32, 127),
])
def test_flash_decode_kernel(B, S, H, KH, D, bk, pos):
    from repro.kernels.flash_decode import kernel as K, ref as R
    q = jnp.asarray(RNG.standard_normal((B, H, D)), jnp.float32)
    kc = jnp.asarray(RNG.standard_normal((B, S, KH, D)), jnp.bfloat16)
    vc = jnp.asarray(RNG.standard_normal((B, S, KH, D)), jnp.bfloat16)
    ref = R.decode_attention_ref(q, kc, vc, jnp.int32(pos))
    got = K.flash_decode(q, kc, vc, jnp.int32(pos), bk=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_flash_decode_matches_model_path():
    from repro.kernels.flash_decode import ops
    q = jnp.asarray(RNG.standard_normal((2, 8, 64)), jnp.float32)
    kc = jnp.asarray(RNG.standard_normal((2, 128, 4, 64)), jnp.float32)
    vc = jnp.asarray(RNG.standard_normal((2, 128, 4, 64)), jnp.float32)
    a = ops.decode_attention(q, kc, vc, jnp.int32(100))
    b = ops.decode_attention(q, kc, vc, jnp.int32(100), use_pallas=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)
