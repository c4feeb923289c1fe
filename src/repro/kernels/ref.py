"""Pure-jnp oracles for every Pallas kernel (the ground truth used by
tests/test_kernels.py shape/dtype sweeps)."""
from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                  bias=None):
    """Naive exact attention. q: (B,Sq,H,hd); k,v: (B,Skv,KV,hd); GQA via
    head grouping. window>0 = sliding causal window. bias: (B,Skv) additive
    (used to mask invalid cache slots)."""
    b, sq, h, hd = q.shape
    skv, n_kv = k.shape[1], k.shape[2]
    g = h // n_kv
    qg = q.reshape(b, sq, n_kv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k.astype(jnp.float32))
    s *= 1.0 / math.sqrt(hd)
    q_pos = jnp.arange(sq)[:, None] + (skv - sq)   # right-aligned
    kv_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kv_pos <= q_pos
    if window:
        mask &= kv_pos > q_pos - window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    if bias is not None:
        s = s + bias[:, None, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(b, sq, h, hd).astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, bias):
    """q: (B,H,hd); caches: (B,S,KV,hd); bias: (B,S) additive mask."""
    b, h, hd = q.shape
    o = attention_ref(q[:, None], k_cache, v_cache, causal=False, bias=bias)
    return o[:, 0]


def int8_matmul_ref(x_q, sx, w_q, sw):
    """x_q: (M,K) int8; sx: (M,1) f32; w_q: (K,N) int8; sw: (1,N) f32."""
    acc = jax.lax.dot_general(x_q, w_q, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def quantize_ref(x, axis=-1):
    """Symmetric per-row int8 quantization -> (x_q, scale)."""
    amax = jnp.max(jnp.abs(x).astype(jnp.float32), axis=axis, keepdims=True) + 1e-8
    s = amax / 127.0
    x_q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127).astype(jnp.int8)
    return x_q, s


def first_argmax_ref(x):
    """First-index argmax along the last axis via two vectorized
    reduces (max, then masked index-min) — bit-identical tie-breaking
    to ``jnp.argmax`` but ~2x faster on CPU XLA, whose native argmax
    lowers to a non-vectorized reduce. Shared by the fused RL ops."""
    k = x.shape[-1]
    m = jnp.max(x, axis=-1, keepdims=True)
    iota = jnp.arange(k, dtype=jnp.int32)
    iota = iota.reshape((1,) * (x.ndim - 1) + (k,))
    return jnp.min(jnp.where(x == m, iota, k), axis=-1).astype(jnp.int32)


def fused_tabular_ref(q, s, a, r, s2, *, alpha: float, gamma: float):
    """Fused tabular act+update oracle (one pass per fleet step).

    ``q``: (cells, S, K) f32; ``s``/``a``/``s2``: (cells,) int32;
    ``r``: (cells,) f32. Returns ``(q_new, greedy2, td)`` where

    * ``td = r + gamma * max_k q[c, s2] - q[c, s, a]`` (the TD error
      against the PRE-update table, exactly the unfused step's),
    * ``q_new = q`` with ``alpha * td`` added at ``(c, s, a)``,
    * ``greedy2 = argmax_k q_new[c, s2]`` — the next step's greedy
      action, computed on the POST-update row (when ``s2 == s`` the
      freshly written ``(s, a)`` entry participates), so the caller can
      carry it through a scan instead of re-gathering the row.
    """
    cells = jnp.arange(q.shape[0])
    k = q.shape[2]
    q_sa = q[cells, s, a]
    row2 = q[cells, s2]                                    # (cells, K)
    iota = jnp.arange(k, dtype=jnp.int32)[None, :]
    # Mask the (possibly) updated entry out of the row ONCE; both the
    # pre-update TD max and the post-update greedy then derive from TWO
    # row reduces + scalar fixups (the naive formulation needs three:
    # max(row2), then max + masked index-min over the updated row).
    same = s2 == s
    hit = same[:, None] & (iota == a[:, None])
    masked = jnp.where(hit, -jnp.inf, row2)
    m_ex = jnp.max(masked, axis=-1)                        # reduce 1
    i_ex = jnp.min(jnp.where(masked == m_ex[:, None], iota, k),
                   axis=-1).astype(jnp.int32)              # reduce 2
    # max is exact, so composing it is bit-identical to max(row2)
    m_pre = jnp.where(same, jnp.maximum(m_ex, q_sa), m_ex)
    td = r + gamma * m_pre - q_sa
    upd = alpha * td
    q_new = q.at[cells, s, a].add(upd)
    # first-index argmax of the post-update row, scalar-wise: the row
    # is `masked` plus (when s2 == s) the fresh value at column a
    v_new = q_sa + upd
    a32 = a.astype(jnp.int32)
    g_same = jnp.where(v_new > m_ex, a32,
                       jnp.where(v_new == m_ex, jnp.minimum(a32, i_ex),
                                 i_ex))
    greedy2 = jnp.where(same, g_same, i_ex)
    return q_new, greedy2, td


def dqn_head_ref(active, member, end_b, agg, w1, b1, w2, b2, w3, b3,
                 allowed, acc_table, *, threshold: float, topk: int):
    """Fused featurize + constraint-aware greedy head oracle.

    ``active``/``member``/``end_b``: (cells, N) f32 per-user blocks;
    ``agg``: (cells, 8) f32 cell aggregates (see
    ``fleet.policy.fused_head_features``); ``w*``/``b*``: the 3-layer
    shared per-user MLP; ``allowed``: (N, A) f32 allowed-action mask
    (disallowed entries become exactly NEG_INF, matching the legacy
    head's where-mask bit for bit); ``acc_table``: (A,) f32 per-action
    accuracy ladder. Returns ``(dec, q)``: (cells, N) int32 greedy
    per-user decisions and the (cells, N, A) masked head values.

    Assembles each user's ``[act, mem, end, agg...]`` feature row
    directly (never materializing the flat ``encode_fleet_state``
    vector), applies the shared MLP, masks, and — with a QoS
    ``threshold`` — picks among the per-user top-k combinations with
    ``combo_pick``.
    """
    cells, n = active.shape
    feats = jnp.concatenate(
        [active[..., None], member[..., None], end_b[..., None],
         jnp.broadcast_to(agg[:, None, :],
                          (cells, n, agg.shape[-1]))], -1)
    x = feats.reshape(cells * n, feats.shape[-1])
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    h = jax.nn.relu(dot(x, w1) + b1)
    h = jax.nn.relu(dot(h, w2) + b2)
    q = jnp.where(allowed[None] > 0.5,
                  (dot(h, w3) + b3).reshape(cells, n, -1), NEG_INF)
    plain = first_argmax_ref(q)
    if not threshold:
        return plain, q
    # lax.top_k has the exact ordering of the kernel's iterative top-k
    # (descending values, ties by ascending index); on rows with fewer
    # than k finite entries the two diverge only in duplicated NEG_INF
    # candidate ids, which combo_pick culls on both paths — decisions
    # stay bit-identical
    vals, idx = jax.lax.top_k(q, topk)                 # (cells, N, k)
    return combo_pick(vals, idx, member, plain, acc_table,
                      threshold=threshold), q


def combo_pick(vals, idx, member, plain, acc_table, *, threshold: float):
    """Constraint-aware pick among the per-user top-k combinations,
    exactly like ``FleetDQN._make_greedy``'s legacy head.

    ``vals``/``idx``: (cells, N, k) per-user top-k values and action
    ids; ``member``: (cells, N) f32/bool; ``plain``: (cells, N) plain
    per-user argmax; ``acc_table``: (A,) accuracy ladder. Scores all
    ``k^N`` combinations: combos with a masked (NEG_INF) member entry
    are culled, combos whose mean member accuracy misses ``threshold``
    are culled, and a cell with no feasible combo falls back to
    ``plain``. Shared by the oracle and the kernel path, which computes
    ``vals``/``idx`` in the Pallas kernel."""
    cells, n, k = vals.shape
    acc_k = acc_table[idx]                             # (cells, N, k)
    combos = np.asarray(list(itertools.product(range(k), repeat=n)),
                        np.int32)                      # (Kc, N) static
    mem = member > 0.5
    nm = jnp.maximum(mem.sum(-1), 1)[:, None].astype(vals.dtype)
    score = jnp.zeros((cells, len(combos)), vals.dtype)
    macc_sum = jnp.zeros((cells, len(combos)), vals.dtype)
    invalid = jnp.zeros((cells, len(combos)), bool)
    for u in range(n):
        cu = combos[:, u]                              # static gather
        v_u, a_u = vals[:, u, cu], acc_k[:, u, cu]     # (cells, Kc)
        m_u = mem[:, u:u + 1]
        score = score + jnp.where(m_u, v_u, 0.0)
        macc_sum = macc_sum + jnp.where(m_u, a_u, 0.0)
        invalid = invalid | ((v_u < -1e29) & m_u)
    macc = jnp.where(mem.any(-1, keepdims=True), macc_sum / nm, 100.0)
    feas = macc >= threshold - 1e-9        # dynamics.feasible, inlined
    score = jnp.where(feas & ~invalid, score, -jnp.inf)
    j = first_argmax_ref(score)                        # (cells,)
    cu_j = jnp.asarray(combos)[j]                      # (cells, N)
    best = jnp.take_along_axis(idx, cu_j[..., None], 2)[..., 0]
    has_feasible = jnp.isfinite(
        jnp.take_along_axis(score, j[:, None], 1))[:, 0]
    return jnp.where(has_feasible[:, None], best, plain)


def selective_scan_ref(u, dt, A, B, C, D):
    """Sequential (lax.scan over time) selective-SSM oracle.

    u, dt: (Bt,S,di); A: (di,N); B,C: (Bt,S,N); D: (di,).
    Returns (y: (Bt,S,di), h_last: (Bt,di,N)); all math in f32.
    """
    uf, dtf = u.astype(jnp.float32), dt.astype(jnp.float32)
    Bf, Cf = B.astype(jnp.float32), C.astype(jnp.float32)

    def step(h, inp):
        ut, dtt, bt, ct = inp          # (Bt,di),(Bt,di),(Bt,N),(Bt,N)
        dA = jnp.exp(dtt[..., None] * A[None])           # (Bt,di,N)
        dBu = (dtt * ut)[..., None] * bt[:, None, :]
        h = h * dA + dBu
        y = jnp.einsum("bdn,bn->bd", h, ct)
        return h, y

    h0 = jnp.zeros((u.shape[0], u.shape[2], A.shape[1]), jnp.float32)
    h_last, ys = jax.lax.scan(step, h0,
                              (uf.swapaxes(0, 1), dtf.swapaxes(0, 1),
                               Bf.swapaxes(0, 1), Cf.swapaxes(0, 1)))
    y = ys.swapaxes(0, 1) + uf * D[None, None]
    return y.astype(u.dtype), h_last
