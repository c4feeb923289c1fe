"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype, key):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,skv,h,kv,hd,causal,window", [
    (2, 128, 128, 8, 2, 64, True, 0),
    (1, 100, 100, 4, 4, 32, True, 48),     # ragged + sliding window
    (2, 64, 192, 6, 3, 128, False, 0),     # cross attention
    (1, 256, 256, 2, 1, 256, True, 0),     # MQA, big head
    (3, 33, 65, 5, 5, 16, True, 0),        # odd everything
])
def test_flash_attention(dtype, b, sq, skv, h, kv, hd, causal, window):
    ks = jax.random.split(KEY, 3)
    q = _rand((b, sq, h, hd), dtype, ks[0])
    k = _rand((b, skv, kv, hd), dtype, ks[1])
    v = _rand((b, skv, kv, hd), dtype, ks[2])
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              bq=32, bk=32)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,hd,s,window,bk", [
    (3, 8, 2, 64, 300, 64, 128),
    (1, 16, 16, 128, 1024, 0, 256),
    (2, 4, 1, 32, 96, 0, 32),
])
def test_decode_attention(dtype, b, h, kv, hd, s, window, bk):
    ks = jax.random.split(KEY, 3)
    q = _rand((b, h, hd), dtype, ks[0])
    kc = _rand((b, s, kv, hd), dtype, ks[1])
    vc = _rand((b, s, kv, hd), dtype, ks[2])
    kv_pos = jnp.tile(jnp.arange(s)[None], (b, 1))
    cur = jnp.asarray(np.random.default_rng(0).integers(1, s, b))
    out = ops.decode_attention(q, kc, vc, kv_pos, cur, window=window, bk=bk)
    valid = (kv_pos >= 0) & (kv_pos <= cur[:, None])
    if window:
        valid &= kv_pos > cur[:, None] - window
    bias = jnp.where(valid, 0.0, -1e30)
    want = ref.decode_attention_ref(q, kc, vc, bias)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("m,k,n,bm", [(100, 200, 300, 64), (128, 128, 128, 128),
                                      (17, 333, 65, 32)])
def test_int8_matmul(m, k, n, bm):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (m, k))
    w = jax.random.normal(ks[1], (k, n))
    xq, sx = ref.quantize_ref(x)
    wq, sw = ref.quantize_ref(w, axis=0)
    out = ops.int8_matmul(xq, sx, wq, sw, bm=bm, bn=64, bk=64)
    want = ref.int8_matmul_ref(xq, sx, wq, sw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-5)


def test_int8_quant_error_bound():
    x = jax.random.normal(KEY, (64, 512))
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 256))
    xq, sx = ref.quantize_ref(x)
    wq, sw = ref.quantize_ref(w, axis=0)
    approx = ops.int8_matmul(xq, sx, wq, sw)
    exact = x @ w
    rel = float(jnp.linalg.norm(approx - exact) / jnp.linalg.norm(exact))
    assert rel < 0.02, rel    # int8 symmetric quant keeps ~1% error here


@pytest.mark.parametrize("bt,s,di,n,bd", [(2, 64, 96, 16, 32),
                                          (1, 128, 64, 8, 64),
                                          (3, 37, 48, 16, 16)])
def test_selective_scan(bt, s, di, n, bd):
    ks = jax.random.split(KEY, 5)
    u = jax.random.normal(ks[0], (bt, s, di)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bt, s, di))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (di, n)) * 0.3)
    B = jax.random.normal(ks[3], (bt, s, n))
    C = jax.random.normal(ks[4], (bt, s, n))
    D = jnp.ones((di,))
    y, h = ops.selective_scan(u, dt, A, B, C, D, bd=bd)
    y2, h2 = ref.selective_scan_ref(u, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h2), atol=1e-4)


def test_assoc_scan_matches_sequential_oracle():
    """models/mamba.py's associative scan == ref.py's sequential scan."""
    from repro.models.mamba import selective_scan_ref as assoc
    ks = jax.random.split(KEY, 5)
    bt, s, di, n = 2, 50, 32, 8
    u = jax.random.normal(ks[0], (bt, s, di)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bt, s, di))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (di, n)) * 0.3)
    B = jax.random.normal(ks[3], (bt, s, n))
    C = jax.random.normal(ks[4], (bt, s, n))
    D = jnp.ones((di,))
    y1, h1 = assoc(u, dt, A, B, C, D)
    y2, h2 = ref.selective_scan_ref(u, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-4)


# ------------------------------------------------ fused tabular RL --------
ALPHA, GAMMA = 0.9, 0.1


def _naive_tabular(q, s, a, r, s2, alpha=ALPHA, gamma=GAMMA):
    """The legacy unfused composition (population.py's xla step +
    next-step gather/argmax), the semantic oracle for the fused op."""
    cells = jnp.arange(q.shape[0])
    td = r + gamma * q[cells, s2].max(-1) - q[cells, s, a]
    q_new = q.at[cells, s, a].add(alpha * td)
    greedy2 = q_new[cells, s2].argmax(-1).astype(jnp.int32)
    return q_new, greedy2, td


def _tabular_case(cells, states=9, k=10, seed=0, ties=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (cells, states, k), jnp.float32)
    if ties:                       # constant rows force argmax tie-breaks
        q = q.at[:, :, :].set(jnp.round(q * 2.0) / 2.0)
        q = q.at[0].set(1.0)
    s = jax.random.randint(ks[1], (cells,), 0, states).astype(jnp.int32)
    a = jax.random.randint(ks[2], (cells,), 0, k).astype(jnp.int32)
    s2 = jax.random.randint(ks[3], (cells,), 0, states).astype(jnp.int32)
    # half the fleet lands on s2 == s: the fused path's hard case (the
    # freshly written entry participates in the next greedy)
    s2 = jnp.where(jnp.arange(cells) % 2 == 0, s, s2)
    r = -jax.random.uniform(ks[4], (cells,), jnp.float32)
    return q, s, a, r, s2


@pytest.mark.parametrize("cells,ties", [(1, False), (13, False),
                                        (64, False), (37, True)])
def test_fused_tabular_ref_matches_naive_composition(cells, ties):
    """The 2-reduce fused formulation is BIT-identical to the legacy
    gather/max/scatter/argmax chain — q, TD error, and next greedy,
    including forced-tie rows (first-index tie-break)."""
    q, s, a, r, s2 = _tabular_case(cells, ties=ties)
    q1, g1, td1 = _naive_tabular(q, s, a, r, s2)
    q2, g2, td2 = ref.fused_tabular_ref(q, s, a, r, s2, alpha=ALPHA,
                                        gamma=GAMMA)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
    np.testing.assert_array_equal(np.asarray(td1), np.asarray(td2))


@pytest.mark.parametrize("cells,bc", [(1, 8), (13, 8), (37, 8), (64, 16)])
def test_tabular_kernel_parity(cells, bc):
    """Pallas kernel (interpret mode; non-block-multiple shapes exercise
    the padding) vs the jnp oracle: integer leaves (greedy) and the
    untouched Q entries bit-exact; touched floats allclose (the kernel
    lowering may contract the TD fma differently)."""
    q, s, a, r, s2 = _tabular_case(cells, seed=cells)
    want_q, want_g, want_td = ref.fused_tabular_ref(
        q, s, a, r, s2, alpha=ALPHA, gamma=GAMMA)
    got_q, got_g, got_td = ops.fused_tabular_update(
        q, s, a, r, s2, alpha=ALPHA, gamma=GAMMA, impl="pallas", bc=bc,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(want_g), np.asarray(got_g))
    touched = np.zeros(q.shape, bool)
    touched[np.arange(cells), np.asarray(s), np.asarray(a)] = True
    np.testing.assert_array_equal(np.asarray(got_q)[~touched],
                                  np.asarray(q)[~touched])
    np.testing.assert_allclose(np.asarray(got_q), np.asarray(want_q),
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_td), np.asarray(want_td),
                               atol=1e-5, rtol=1e-5)


def test_tabular_kernel_tie_break_first_index():
    """All-equal Q rows: the kernel's greedy must reproduce jnp.argmax's
    first-index tie-break bit-exactly through the padded dispatch."""
    q, s, a, r, s2 = _tabular_case(13, ties=True)
    q = jnp.zeros_like(q)          # every row fully tied
    _, want_g, _ = _naive_tabular(q, s, a, r, s2)
    _, got_g, _ = ops.fused_tabular_update(
        q, s, a, r, s2, alpha=ALPHA, gamma=GAMMA, impl="pallas", bc=8,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(want_g), np.asarray(got_g))


def test_resolve_rl_impl_gating():
    assert ops.resolve_rl_impl("xla") == "xla"
    assert ops.resolve_rl_impl("ref") == "ref"
    assert ops.resolve_rl_impl("pallas_interpret") == "pallas_interpret"
    # GSPMD cannot partition pallas_call: a mesh forces the fused-jnp ref
    assert ops.resolve_rl_impl("pallas", mesh=object()) == "ref"
    assert ops.resolve_rl_impl("pallas") in ("pallas", "ref")
    with pytest.raises(ValueError, match="unknown impl"):
        ops.resolve_rl_impl("cuda")
    with pytest.raises(ValueError, match="no fused op path"):
        ops.rl_op_kwargs("xla")


# ------------------------------------------------- fused DQN head ---------
def _dqn_params(users, hidden=16, seed=0, n_act=10):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    dims = [11, hidden, hidden, n_act]
    return [{"w": jax.random.normal(ks[2 * i], (dims[i], dims[i + 1]),
                                    jnp.float32) * 0.3,
             "b": jax.random.normal(ks[2 * i + 1], (dims[i + 1],),
                                    jnp.float32) * 0.1}
            for i in range(3)]


def _dqn_case(cells, users, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + 100), 4)
    mem = (jax.random.uniform(ks[0], (cells, users)) < 0.8)
    mem = mem.at[:, 0].set(True)          # never an empty cell
    act = mem & (jax.random.uniform(ks[1], (cells, users)) < 0.7)
    end_b = (jax.random.uniform(ks[2], (cells, users)) < 0.5)
    agg = jax.random.normal(ks[3], (cells, 8), jnp.float32)
    from repro.fleet import dynamics
    acc_table = jnp.asarray(dynamics.accuracies(np.arange(10)),
                            jnp.float32)
    return (act.astype(jnp.float32), mem.astype(jnp.float32),
            end_b.astype(jnp.float32), agg, acc_table)


@pytest.mark.parametrize("cells,users,threshold,bc", [
    (1, 2, 0.0, 16), (37, 3, 0.0, 16),
    (1, 2, 85.0, 16), (37, 3, 85.0, 16),
    (64, 2, 85.0, 64),
    (13, 3, 101.0, 16),       # infeasible goal: every cell falls back
])
def test_dqn_head_kernel_parity(cells, users, threshold, bc):
    """Fused head kernel vs the jnp oracle across the constraint
    regimes (off / active / infeasible-fallback), with padding."""
    act, mem, end_b, agg, acc_table = _dqn_case(cells, users, seed=cells)
    params = _dqn_params(users, seed=users)
    allowed = jnp.ones((users, 10), jnp.float32)
    kw = dict(threshold=threshold, topk=3)
    want_d, want_q = ops.dqn_head(act, mem, end_b, agg, params, allowed,
                                  acc_table, impl="ref", **kw)
    got_d, got_q = ops.dqn_head(act, mem, end_b, agg, params, allowed,
                                acc_table, impl="pallas", bc=bc,
                                interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(want_d), np.asarray(got_d))
    np.testing.assert_allclose(np.asarray(want_q), np.asarray(got_q),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("threshold", [0.0, 85.0])
def test_dqn_head_masked_rows_parity(threshold):
    """Sparse allowed-action masks — one user with fewer allowed actions
    than topk (exhausted top-k rows) and one all-masked user — keep the
    kernel bit-identical to the oracle on decisions."""
    cells, users = 29, 3
    act, mem, end_b, agg, acc_table = _dqn_case(cells, users, seed=7)
    params = _dqn_params(users, seed=3)
    allowed = np.ones((users, 10), np.float32)
    allowed[0, 2:] = 0.0          # 2 allowed < topk=3: exhausted rows
    allowed[1, :] = 0.0           # all-masked user
    allowed = jnp.asarray(allowed)
    kw = dict(threshold=threshold, topk=3)
    want_d, want_q = ops.dqn_head(act, mem, end_b, agg, params, allowed,
                                  acc_table, impl="ref", **kw)
    got_d, got_q = ops.dqn_head(act, mem, end_b, agg, params, allowed,
                                acc_table, impl="pallas", bc=16,
                                interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(want_d), np.asarray(got_d))
    np.testing.assert_allclose(np.asarray(want_q), np.asarray(got_q),
                               atol=1e-5, rtol=1e-5)


def test_dqn_head_block_cells_fits_vmem_budget():
    """The fused head's block size comes from a scoped-VMEM budget: a
    multiple of 8, shrinking as the head widens or top-k planes grow,
    never below one sublane tile."""
    from repro.kernels.dqn_head import VMEM_BUDGET, block_cells
    bc = block_cells(5, 128, 10, 5)
    assert bc % 8 == 0 and 8 <= bc <= 512
    assert block_cells(5, 128, 10, 0) >= bc           # no top-k planes
    assert block_cells(5, 512, 10, 5) <= bc           # wider hidden
    assert block_cells(5, 128, 10, 5, budget=VMEM_BUDGET // 4) < bc
    assert block_cells(5, 4096, 10, 5, budget=1) == 8


def test_dqn_head_parts_name_the_split():
    assert ops.dqn_head_parts("pallas", 85.0) == {"head": "pallas",
                                                  "combo_pick": "xla"}
    assert ops.dqn_head_parts("pallas", 0.0) == {"head": "pallas"}
    assert ops.dqn_head_parts("ref", 85.0) == {"head": "ref",
                                               "combo_pick": "ref"}


def test_dqn_head_infeasible_falls_back_to_plain_argmax():
    act, mem, end_b, agg, acc_table = _dqn_case(17, 2, seed=5)
    params = _dqn_params(2, seed=5)
    allowed = jnp.ones((2, 10), jnp.float32)
    dec, q = ops.dqn_head(act, mem, end_b, agg, params, allowed,
                          acc_table, threshold=101.0, topk=3, impl="ref")
    np.testing.assert_array_equal(np.asarray(dec),
                                  np.asarray(ref.first_argmax_ref(q)))


# ---------------------------------------------- hypothesis properties -----
def test_property_fused_tabular_preserves_untouched_entries():
    """Fused update may only write the (cell, s, a) scatter targets —
    every other Q entry must come back bit-identical."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 33))
    def prop(seed, cells):
        q, s, a, r, s2 = _tabular_case(cells, seed=seed % 10_000)
        q_new, _, _ = ops.fused_tabular_update(
            q, s, a, r, s2, alpha=ALPHA, gamma=GAMMA, impl="ref")
        touched = np.zeros(q.shape, bool)
        touched[np.arange(cells), np.asarray(s), np.asarray(a)] = True
        np.testing.assert_array_equal(np.asarray(q_new)[~touched],
                                      np.asarray(q)[~touched])

    prop()


def test_property_dqn_head_respects_allowed_mask():
    """The constraint head never emits an action outside a member
    user's allowed set (when that user has any allowed action at all),
    at any threshold — the PR-2 constraint-leak invariant."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 17),
           st.integers(2, 3), st.sampled_from([0.0, 85.0]))
    def prop(seed, cells, users, threshold):
        act, mem, end_b, agg, acc_table = _dqn_case(cells, users,
                                                    seed=seed % 10_000)
        params = _dqn_params(users, seed=seed % 97)
        rng = np.random.default_rng(seed)
        allowed = (rng.random((users, 10)) < 0.6)
        allowed[:, 0] = True          # every user keeps >= 1 action
        dec, _ = ops.dqn_head(act, mem, end_b, agg, params,
                              jnp.asarray(allowed, jnp.float32),
                              acc_table, threshold=threshold, topk=3,
                              impl="ref")
        dec = np.asarray(dec)
        member = np.asarray(mem) > 0.5
        assert allowed[np.arange(users)[None, :], dec][member].all()

    prop()
