"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle, sweeping
shapes and dtypes (the repo-wide kernel contract)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

RNG = np.random.default_rng(42)


def _randn(shape, dtype, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale).astype(dtype)


# ----------------------------- pulse_chase ----------------------------------


@pytest.mark.parametrize("wave", [4, 8])
@pytest.mark.parametrize("n_keys,n_queries", [(128, 16), (512, 32)])
def test_pulse_chase_btree_matches_ref(wave, n_keys, n_queries):
    from repro.core.structures import btree
    from repro.kernels.pulse_chase import ops

    keys = RNG.choice(np.arange(10**5), size=n_keys, replace=False).astype(np.int32)
    values = RNG.integers(0, 10**6, n_keys).astype(np.int32)
    ar, root, height = btree.build(keys, values)
    it = btree.find_iterator()
    q = np.concatenate(
        [keys[: n_queries // 2],
         RNG.integers(10**5, 10**6, n_queries // 2).astype(np.int32)]
    )
    ptr0, scr0 = it.init(jnp.asarray(q), root)
    status0 = jnp.zeros(n_queries, jnp.int32)
    logic = ops.iterator_logic(it)
    r_ref = ops.pulse_chase(
        ar.data, ptr0, scr0, status0, logic_fn=logic, num_steps=height,
        use_pallas=False,
    )
    r_pal = ops.pulse_chase(
        ar.data, ptr0, scr0, status0, logic_fn=logic, num_steps=height,
        wave=wave, use_pallas=True, interpret=True,
    )
    for a, b, nm in zip(r_ref, r_pal, ["ptr", "scratch", "status", "iters"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=nm)
    assert (np.asarray(r_pal[2]) == 1).all()  # all done within height steps
    found = np.asarray(r_pal[1])[:, 2]
    assert found[: n_queries // 2].all() and not found[n_queries // 2 :].any()


def test_pulse_chase_hash_chain(
):
    from repro.core.structures import hash_table
    from repro.kernels.pulse_chase import ops

    keys = RNG.choice(np.arange(10**5), size=256, replace=False).astype(np.int32)
    values = RNG.integers(0, 10**6, 256).astype(np.int32)
    ar, heads = hash_table.build(keys, values, 32)
    it = hash_table.find_iterator(32)
    ptr0, scr0 = it.init(jnp.asarray(keys[:32]), jnp.asarray(heads))
    status0 = jnp.zeros(32, jnp.int32)
    logic = ops.iterator_logic(it)
    r_ref = ops.pulse_chase(ar.data, ptr0, scr0, status0, logic_fn=logic,
                            num_steps=32, use_pallas=False)
    r_pal = ops.pulse_chase(ar.data, ptr0, scr0, status0, logic_fn=logic,
                            num_steps=32, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(r_ref[1]), np.asarray(r_pal[1]))
    assert np.asarray(r_pal[1])[:, 2].all()


def test_pulse_chase_wave_iters_exact_vs_xla():
    """The wave-scheduled kernel path must report EXACT per-lane iteration
    counts (not chunk-granular upper bounds): engine backend="kernel" and
    the XLA executor agree bit-for-bit on iters for done and NULL-terminated
    lanes, so downstream hop accounting stops over-counting."""
    from repro.core.engine import PulseEngine
    from repro.core.iterator import STATUS_DONE
    from repro.core.structures import hash_table, linked_list

    keys = RNG.choice(np.arange(10**5), size=256, replace=False).astype(np.int32)
    values = RNG.integers(0, 10**6, 256).astype(np.int32)
    ar, heads = hash_table.build(keys, values, 8)
    it = hash_table.find_iterator(8)
    q = np.concatenate(
        [keys[:24], RNG.integers(10**5, 10**6, 8).astype(np.int32)]
    )
    ptr0, scr0 = it.init(jnp.asarray(q), jnp.asarray(heads))
    eng = PulseEngine(ar)
    rx = eng.execute(it, ptr0, scr0, max_iters=256, backend="xla")
    rk = eng.execute(it, ptr0, scr0, max_iters=256, backend="kernel")
    np.testing.assert_array_equal(np.asarray(rk.scratch), np.asarray(rx.scratch))
    np.testing.assert_array_equal(np.asarray(rk.status), np.asarray(rx.status))
    np.testing.assert_array_equal(
        np.asarray(rk.iters), np.asarray(rx.iters), err_msg="exact per-lane iters"
    )
    # skewed depths actually exercise multiple retirement waves
    assert rk.stats.chunks > 1 and np.unique(np.asarray(rk.iters)).size > 2

    keys = np.arange(64, dtype=np.int32)
    ar, head = linked_list.build(keys, keys * 7)
    it = linked_list.find_iterator()
    ptr0, scr0 = it.init(jnp.asarray(keys[::4]), head)
    eng = PulseEngine(ar)
    rx = eng.execute(it, ptr0, scr0, max_iters=4096, backend="xla")
    rk = eng.execute(it, ptr0, scr0, max_iters=4096, backend="kernel")
    assert (np.asarray(rx.status) == STATUS_DONE).all()
    np.testing.assert_array_equal(np.asarray(rk.iters), np.asarray(rx.iters))


@pytest.mark.parametrize(
    "name", ["hash", "btree", "btree_range", "list", "skiplist", "bst"]
)
def test_lane_columns_match_vmap(name):
    """The lane-columnar body the compiled kernel runs is bit-identical to
    the vmap of the per-lane iterator body, on arbitrary node contents (so
    clamped dynamic indices, empty/overfull key counts and misses are all
    exercised, not just well-formed nodes)."""
    from repro.core.structures import bst, btree, hash_table, linked_list, skiplist
    from repro.kernels.pulse_chase import ops

    it = {
        "hash": lambda: hash_table.find_iterator(64),
        "btree": btree.find_iterator,
        "btree_range": btree.range_aggregate_iterator,
        "list": linked_list.find_iterator,
        "skiplist": skiplist.find_iterator,
        "bst": bst.find_iterator,
    }[name]()
    logic = ops.iterator_logic(it)
    G, W, S = 64, 20, it.scratch_words
    rng = np.random.default_rng(7)
    nodes = rng.integers(-12, 12, (G, W)).astype(np.int32)
    nodes[: G // 2] = rng.integers(-(2**31), 2**31 - 1, (G // 2, W), dtype=np.int64)
    ptr = rng.integers(-1, 1000, G).astype(np.int32)
    scr = rng.integers(-12, 12, (G, S)).astype(np.int32)
    scr[:, 0] = nodes[np.arange(G), rng.integers(0, W, G)]  # some key hits
    want = logic(jnp.asarray(nodes), jnp.asarray(ptr), jnp.asarray(scr))
    got = jax.jit(logic.columns)(
        jnp.asarray(nodes), jnp.asarray(ptr)[:, None], jnp.asarray(scr)
    )
    np.testing.assert_array_equal(np.asarray(got[0])[:, 0], np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1])[:, 0], np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))


# --------------------------- flash_attention --------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,Hk,Lq,Lk,D,causal",
    [
        (2, 4, 2, 128, 128, 64, True),
        (1, 4, 4, 256, 256, 32, True),
        (2, 2, 1, 128, 256, 64, True),  # decode-style Lq < Lk
        (1, 4, 2, 128, 128, 64, False),  # bidirectional (encoder)
    ],
)
@pytest.mark.slow
def test_flash_attention_matches_ref(B, H, Hk, Lq, Lk, D, causal, dtype):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import mha_reference

    q = _randn((B, H, Lq, D), dtype)
    k = _randn((B, Hk, Lk, D), dtype)
    v = _randn((B, Hk, Lk, D), dtype)
    o_ref = mha_reference(q, k, v, causal=causal)
    o_pal = flash_attention(q, k, v, causal, 64, 64, True, True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(o_ref, np.float32), np.asarray(o_pal, np.float32),
        atol=tol, rtol=tol,
    )


@pytest.mark.slow
def test_flash_attention_grad_matches_ref():
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import mha_reference

    q = _randn((1, 2, 128, 32), jnp.float32)
    k = _randn((1, 2, 128, 32), jnp.float32)
    v = _randn((1, 2, 128, 32), jnp.float32)
    g1 = jax.grad(lambda q: flash_attention(q, k, v, True, 64, 64, True, True).sum())(q)
    g2 = jax.grad(lambda q: mha_reference(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=2e-5, rtol=2e-5)


# --------------------------- paged_attention --------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,Hk,D,page,P,N",
    [
        (2, 4, 2, 64, 16, 4, 32),
        (1, 8, 8, 32, 8, 8, 64),
        (3, 4, 1, 64, 16, 3, 16),
    ],
)
@pytest.mark.slow
def test_paged_attention_matches_ref(B, H, Hk, D, page, P, N, dtype):
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_reference

    q = _randn((B, H, D), dtype)
    kp = _randn((N, page, Hk, D), dtype)
    vp = _randn((N, page, Hk, D), dtype)
    pt = jnp.asarray(RNG.integers(0, N, (B, P)), jnp.int32)
    lengths = jnp.asarray(RNG.integers(1, P * page + 1, (B,)), jnp.int32)
    o_ref = paged_attention_reference(q, kp, vp, pt, lengths)
    o_pal = paged_attention(q, kp, vp, pt, lengths, interpret=True, use_pallas=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(o_ref, np.float32), np.asarray(o_pal, np.float32),
        atol=tol, rtol=tol,
    )


# ------------------------------ ssd_scan ------------------------------------


@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("Bt,L,H,dh,N", [(2, 256, 3, 32, 16), (1, 128, 2, 64, 64)])
@pytest.mark.slow
def test_ssd_kernel_matches_chunked_ref(Bt, L, H, dh, N, chunk):
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_chunked_batched

    x = _randn((Bt, L, H, dh), jnp.float32, 0.5)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (Bt, L, H)), jnp.float32)
    A = jnp.asarray(RNG.uniform(-1.0, -0.1, (H,)), jnp.float32)
    B = _randn((Bt, L, N), jnp.float32, 0.5)
    C = _randn((Bt, L, N), jnp.float32, 0.5)
    yr, Sr = ssd_chunked_batched(x, dt, A, B, C, chunk=chunk)
    yk, Sk = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True, use_pallas=True)
    np.testing.assert_allclose(np.asarray(yr), np.asarray(yk), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(Sr), np.asarray(Sk), atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_ssd_chunked_equals_sequential_recurrence():
    from repro.kernels.ssd_scan.ref import ssd_chunked, ssd_sequential

    L, dh, N = 256, 32, 16
    x = _randn((L, dh), jnp.float32, 0.5)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (L,)), jnp.float32)
    A = jnp.float32(-0.7)
    B = _randn((L, N), jnp.float32, 0.5)
    C = _randn((L, N), jnp.float32, 0.5)
    y1, S1 = ssd_sequential(x, dt, A, B, C)
    y2, S2 = ssd_chunked(x, dt, A, B, C, chunk=64)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S2), atol=1e-4, rtol=1e-4)
