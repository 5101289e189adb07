"""Serve-layer tests: the token engine's decode contract and the
QR-as-a-service continuous-batching front end (``repro.serve.qr_service``).

The qr_service acceptance oracle: every tenant's retired R must be
BITWISE-identical to the R the service retires for the same request held
alone and failure-free (the same compiled panel programs), and that R must
satisfy the float64 Gram identity — whether the request drained alone, in
a full resident batch, joined mid-stream, or survived a mid-batch lane
kill."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import save as ckpt_save
from repro.configs import get_smoke
from repro.core import SimComm
from repro.serve import Engine, QRService, ServeConfig
from repro.serve.engine import _prefill_to_decode_caches
from repro.models import api, attention as attn, transformer as tf

P = 4
B_PANEL = 4
BUCKET = (8, 14)  # (m_loc, n_bucket): fits m <= 32, n + nrhs <= 14


# -- qr_service --------------------------------------------------------------


def _solo_R(comm, A, rhs):
    """The acceptance oracle: the tenant's R from a failure-free service
    that holds it alone, checked against the float64 Gram matrix."""
    svc = QRService(comm, panel_width=B_PANEL, buckets=[BUCKET], max_slots=1)
    rid = svc.submit(A, rhs)
    R = svc.run_until_drained()[rid].R
    Af = A.astype(np.float64)
    G = Af.T @ Af
    Rd = R.astype(np.float64)
    np.testing.assert_allclose(Rd.T @ Rd, G, rtol=0,
                               atol=1e-4 * np.abs(G).max())
    return R


def _requests(rng, count=5):
    shapes = [(10, 6), (16, 12), (7, 10), (24, 9), (12, 12)][:count]
    out = []
    for i, (m, n) in enumerate(shapes):
        A = rng.standard_normal((m, n)).astype(np.float32)
        rhs = (rng.standard_normal((m, 2)).astype(np.float32)
               if i == 0 else None)
        out.append((A, rhs))
    return out


def test_qr_service_admission_retire_bitwise(rng):
    """Staggered admission under slot pressure: requests queue FIFO, join
    at panel boundaries, retire early, and every R is bitwise-solo."""
    comm = SimComm(P)
    svc = QRService(comm, panel_width=B_PANEL, buckets=[BUCKET], max_slots=2)
    reqs = _requests(rng)
    rids = [svc.submit(A, rhs) for A, rhs in reqs[:3]]
    svc.tick()
    assert svc.resident <= 2 and len(svc.queue) >= 1  # capacity respected
    rids += [svc.submit(A, rhs) for A, rhs in reqs[3:]]
    results = svc.run_until_drained()
    assert set(rids) == set(results)
    for rid, (A, rhs) in zip(rids, reqs):
        res = results[rid]
        assert res.R.shape == (min(A.shape), A.shape[1])
        np.testing.assert_array_equal(res.R, _solo_R(comm, A, rhs))
        assert res.panels == -(-min(A.shape) // B_PANEL)  # early retirement


def test_qr_service_kill_mid_batch_heals(rng):
    """A lane killed under load: every resident tenant is REBUILDed from
    its buddies and still retires the bitwise failure-free R."""
    comm = SimComm(P)
    svc = QRService(comm, panel_width=B_PANEL, buckets=[BUCKET], max_slots=4)
    reqs = _requests(rng)
    rids = [svc.submit(A, rhs) for A, rhs in reqs]
    svc.tick()   # admit + advance the first wave one panel
    svc.tick()
    svc.kill_lane(2)  # lands at the next boundary, mid-batch
    results = svc.run_until_drained()
    healed = sum(len(results[r].events) for r in rids)
    assert healed >= 1, "the kill was never detected/healed"
    for rid, (A, rhs) in zip(rids, reqs):
        np.testing.assert_array_equal(results[rid].R, _solo_R(comm, A, rhs))


def test_qr_service_lstsq(rng):
    """The rhs rides the bucket: retirement back-solves the same answer
    as numpy's dense lstsq."""
    comm = SimComm(P)
    svc = QRService(comm, panel_width=B_PANEL, buckets=[BUCKET], max_slots=2)
    A = rng.standard_normal((20, 8)).astype(np.float32)
    rhs = rng.standard_normal((20, 2)).astype(np.float32)
    rid = svc.submit(A, rhs)
    res = svc.run_until_drained()[rid]
    x_ref, *_ = np.linalg.lstsq(A.astype(np.float64),
                                rhs.astype(np.float64), rcond=None)
    np.testing.assert_allclose(res.x, x_ref, atol=1e-3)


def test_qr_service_drain_batched_matches_continuous(rng):
    """The express static-batch path (vmapped bucket dispatch) returns the
    same tenant answers as continuous batching."""
    comm = SimComm(P)
    reqs = _requests(rng)
    svc_c = QRService(comm, panel_width=B_PANEL, buckets=[BUCKET], max_slots=8)
    svc_b = QRService(comm, panel_width=B_PANEL, buckets=[BUCKET], max_slots=8)
    rids_c = [svc_c.submit(A, rhs) for A, rhs in reqs]
    rids_b = [svc_b.submit(A, rhs) for A, rhs in reqs]
    res_c = svc_c.run_until_drained()
    res_b = svc_b.drain_batched()
    for rc, rb in zip(rids_c, rids_b):
        np.testing.assert_allclose(res_b[rb].R, res_c[rc].R,
                                   rtol=1e-5, atol=1e-5)
        if res_c[rc].x is not None:
            np.testing.assert_allclose(res_b[rb].x, res_c[rc].x,
                                       rtol=1e-4, atol=1e-4)


def test_qr_service_no_new_compiles_at_steady_state(rng):
    """The resident-program claim: once one sweep per bucket has warmed the
    segment runner, further traffic (any admission order) compiles nothing."""
    comm = SimComm(P)
    svc = QRService(comm, panel_width=B_PANEL, buckets=[BUCKET], max_slots=3)
    for A, rhs in _requests(rng, 3):
        svc.submit(A, rhs)
    svc.run_until_drained()
    warm = svc.compiled_programs
    for A, rhs in _requests(rng, 5):  # second wave, staggered
        svc.submit(A, rhs)
        svc.tick()
    svc.run_until_drained()
    assert svc.compiled_programs == warm


# -- token engine ------------------------------------------------------------


def test_engine_greedy_determinism(rng):
    """temperature=0 decoding is a pure function of (params, prompts)."""
    cfg = get_smoke("tinyllama-1.1b")
    params = tf.init_params(cfg, jax.random.key(0))
    prompts = rng.integers(0, cfg.vocab, (2, 8)).astype(np.int32)
    engine = Engine(cfg, params, ServeConfig(max_new_tokens=6))
    out1 = engine.generate(prompts)
    out2 = engine.generate(prompts)
    np.testing.assert_array_equal(out1, out2)


def test_engine_eos_masking(rng):
    """A slot that hits EOS keeps decoding into a sink but every
    subsequent output position is masked to eos_id."""
    cfg = get_smoke("tinyllama-1.1b")
    params = tf.init_params(cfg, jax.random.key(0))
    prompts = rng.integers(0, cfg.vocab, (2, 8)).astype(np.int32)
    free = Engine(cfg, params, ServeConfig(max_new_tokens=8)).generate(prompts)
    eos = int(free[0, 2])  # a token row 0 emits mid-stream
    out = Engine(cfg, params, ServeConfig(max_new_tokens=8, eos_id=eos)
                 ).generate(prompts)
    for b in range(out.shape[0]):
        hits = np.flatnonzero(out[b] == eos)
        if hits.size:
            assert (out[b, hits[0]:] == eos).all(), out[b]
    assert (out[0] == eos).any()  # row 0 provably finished early


def test_prefill_decode_parity_sliding_window(rng):
    """Prefill->decode relayout parity on a sliding-window arch with the
    GLOBAL cache length (prompt + new > window): each "L" layer must be
    cropped to ITS window, not the global cache_len — greedy decode then
    reproduces the no-cache reference rollout exactly."""
    cfg = get_smoke("gemma2-2b")
    assert cfg.sliding_window and cfg.sliding_window < 24
    params = tf.init_params(cfg, jax.random.key(1))
    S0, steps = 24, 6
    prompts = rng.integers(0, cfg.vocab, (2, S0)).astype(np.int32)

    # no-cache reference: full forward re-run per generated token
    toks = jnp.asarray(prompts)
    ref = []
    for _ in range(steps):
        hidden, _, _ = tf.forward(cfg, params, toks)
        nxt = jnp.argmax(tf.logits_fn(cfg, params, hidden)[:, -1],
                         axis=-1).astype(jnp.int32)[:, None]
        ref.append(np.asarray(nxt[:, 0]))
        toks = jnp.concatenate([toks, nxt], axis=1)
    ref = np.stack(ref, axis=1)

    out = Engine(cfg, params, ServeConfig(max_new_tokens=steps)
                 ).generate(prompts)
    np.testing.assert_array_equal(out, ref)


def test_prefill_to_decode_layer_window_contract():
    """The module-level contract the serve engine relies on: an "L" layer
    converted with the global cache length lands at ITS window, in rolled
    pos%window order (the pre-fix code used the global length as the
    window, corrupting the addressing whenever they differ)."""
    cfg = get_smoke("gemma2-2b")
    w = cfg.sliding_window
    S0, total = 24, 30
    assert w < S0 < total
    k = jnp.arange(S0, dtype=jnp.float32).reshape(1, S0, 1, 1)
    cache = attn.KVCache(k=jnp.broadcast_to(k, (1, S0, 2, 4)),
                         v=jnp.broadcast_to(k, (1, S0, 2, 4)))
    out = _prefill_to_decode_caches(cfg, cache, S0, total, mixer="L")
    assert out.k.shape[-3] == w, (out.k.shape, w)
    # entry at slot p%w must hold position p, for the last w positions
    got = np.asarray(out.k[0, :, 0, 0])
    want = np.empty(w, np.float32)
    for p in range(S0 - w, S0):
        want[p % w] = p
    np.testing.assert_array_equal(got, want)
    # a global layer with the same call pads to the global length instead
    out_g = _prefill_to_decode_caches(cfg, cache, S0, total, mixer="G")
    assert out_g.k.shape[-3] == total


# -- checkpoint restore (the launch/serve.py fix) ----------------------------


def test_restore_params_roundtrip(tmp_path):
    """Params-only restore round-trips bitwise with NO optimizer skeleton;
    the old ``restore(ckpt, params, params)`` call (params tree passed as
    opt_like) cannot even address the saved optimizer npz."""
    params = {"emb": np.arange(12, dtype=np.float32).reshape(3, 4),
              "head": {"w": np.ones((4, 2), np.float32)}}
    opt = {"mu": jax.tree_util.tree_map(np.zeros_like, params),
           "count": np.int32(7)}
    ckpt_save.save(str(tmp_path), 3, params, opt, extra={"note": "t"})
    like = jax.tree_util.tree_map(np.zeros_like, params)
    got, manifest = ckpt_save.restore_params(str(tmp_path), like)
    assert manifest["step"] == 3
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(KeyError):
        # the bug this replaces: a params-shaped opt_like template
        ckpt_save.restore(str(tmp_path), like, like)
