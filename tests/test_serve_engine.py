"""FT serving engine conformance suite (PR 9).

Three layers, matching the serving stack's three layers:

  * kernel — the per-row ragged paged flash decode kernel vs a float64
    softmax oracle over the gathered pages, across GQA group sizes and
    per-row lengths including 0 and exact page boundaries; deterministic
    in-kernel SEU corrected bit-for-bit on exactly-representable operands;
    detect-only leaves the fault in place but reports it;
  * model — `transformer.paged_decode_step` ≡ the dense `decode_step`
    (logits and post-step cache contents), with a jaxpr audit proving zero
    unprotected dot_generals and the paged decode kernel in the trace;
  * engine — continuous batching conserves outputs: every request decodes
    to exactly its solo-greedy tokens, no request starves, every page
    returns to the free list, and decode-path detections are attributed to
    the `dec_flash` site in the metrics stream.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.core.policy import FTConfig, InjectionSpec
from repro.kernels import ops
from repro.models import transformer as tfm
from repro.models.blocks import Ctx
from repro.tools.metrics import MetricsSink, MemoryEmitter
from repro.train import kv_cache as kvc
from repro.train.engine import EngineConfig, ServeEngine

FT_PALLAS = FTConfig(action="correct", level="block", backend="pallas")
TINY = ModelConfig(arch_id="tiny", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                   head_dim=128)


@pytest.fixture(scope="module")
def tiny_params():
    return tfm.init(TINY, jax.random.PRNGKey(0), jnp.float32)


# ---------------------------------------------------------------------------
# kernel: paged ragged decode vs dense oracle
# ---------------------------------------------------------------------------

def _paged_kv(lengths, kvh, dh, page, mp, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    b = len(lengths)
    n_pages = 1 + b * mp
    cache = kvc.init_paged_cache(1, n_pages, b, mp, kvh, page, dh, dtype)
    alloc = kvc.PageAllocator(n_pages, b, mp, page)
    for length in lengths:
        if length == 0:
            # keep the slot order: claim it with zero pages (all-NULL row)
            alloc.alloc_slot(0)
            continue
        s, _ = alloc.alloc_slot(length)
        ks = jnp.asarray(rng.standard_normal((1, length, kvh, dh)), dtype)
        vs = jnp.asarray(rng.standard_normal((1, length, kvh, dh)), dtype)
        cache = kvc.write_prefill(cache, s, jnp.asarray(alloc.page_table[s]),
                                  ks, vs, length)
    alloc.check_invariants()
    return cache, alloc, rng


def _oracle_row(q_row, kd, vd, length, dh):
    if length == 0:
        return np.zeros(dh)
    kk = kd[:length].astype(np.float64)
    vv = vd[:length].astype(np.float64)
    sc = kk @ q_row.astype(np.float64) * dh ** -0.5
    p = np.exp(sc - sc.max())
    p /= p.sum()
    return p @ vv


@pytest.mark.parametrize("kvh,nrep", [(2, 2), (1, 4), (4, 1)])
@pytest.mark.parametrize("lengths", [[17, 64, 0], [16, 1, 33]])
def test_paged_ragged_decode_matches_oracle(kvh, nrep, lengths):
    """Per-row ragged lengths — including a dead row (0), one token, an
    exact page boundary (16) and full capacity (64) — across GQA group
    sizes, vs the float64 softmax oracle."""
    dh, page, mp = 128, 16, 4
    h = kvh * nrep
    cache, alloc, rng = _paged_kv(lengths, kvh, dh, page, mp,
                                  seed=kvh * 10 + nrep)
    q = jnp.asarray(rng.standard_normal((len(lengths), h, dh)), jnp.float32)
    out, rep = ops.flash_ft_decode(
        q, cache["k_pages"], cache["v_pages"],
        jnp.asarray(alloc.lengths), jnp.asarray(alloc.page_table), 0,
        ft=FTConfig(level="block", action="correct"), interpret=True)
    out = np.asarray(out)
    assert float(np.asarray(rep)[..., 0].sum()) == 0.0, "false positive"
    kd, vd = kvc.gather_dense(cache)
    kd, vd = np.asarray(kd[0]), np.asarray(vd[0])     # (B, S, KVH, dh)
    for slot, length in enumerate(lengths):
        for hh in range(h):
            ref = _oracle_row(np.asarray(q[slot, hh]),
                              kd[slot, :, hh // nrep],
                              vd[slot, :, hh // nrep], length, dh)
            np.testing.assert_allclose(out[slot, hh], ref, atol=2e-5,
                                       rtol=2e-5)


def _exact_paged_kv(lengths, kvh, dh, page, seed=0, n_layers=1):
    """Exactly-representable operands: one-hot 64·e_t queries/keys (matched
    score 256 → softmax weights in {1, 1/2} exactly, dh=256 scale is 2^-4),
    small-integer V — the paged decode output is exact in f32, so a
    corrected SEU must be bit-for-bit identical to the clean run. Each
    layer of an ``n_layers`` pool gets its own V."""
    rng = np.random.default_rng(seed)
    b = len(lengths)
    mp = 512 // page
    n_pages = 1 + b * mp
    cache = kvc.init_paged_cache(n_layers, n_pages, b, mp, kvh, page, dh,
                                 jnp.float32)
    alloc = kvc.PageAllocator(n_pages, b, mp, page)
    for length in lengths:
        s, _ = alloc.alloc_slot(length)
        karr = 64.0 * np.eye(dh, dtype=np.float32)[np.arange(length) % dh]
        ks = jnp.asarray(np.broadcast_to(karr[None, :, None],
                                         (n_layers, length, kvh, dh)).copy())
        vs = jnp.asarray(rng.integers(-2, 3, (n_layers, length, kvh, dh)),
                         jnp.float32)
        cache = kvc.write_prefill(cache, s, jnp.asarray(alloc.page_table[s]),
                                  ks, vs, length)
    tq = rng.integers(0, dh, (b, kvh * 2))
    q = jnp.asarray(64.0 * np.eye(dh, dtype=np.float32)[tq])
    return q, cache, alloc


@pytest.mark.parametrize("n_layers,layer", [(1, 0), (3, 2)])
def test_paged_decode_seu_corrected_bitexact(n_layers, layer):
    """An SEU at one (slot, head) grid row of one layer is detected on that
    row and corrected bit for bit, in a single-layer pool and in layer 2 of
    a stacked one."""
    kvh, dh, page = 2, 256, 16
    q, cache, alloc = _exact_paged_kv([272, 320], kvh, dh, page,
                                      n_layers=n_layers)
    ft = FTConfig(level="block", action="correct")
    args = (q, cache["k_pages"], cache["v_pages"],
            jnp.asarray(alloc.lengths), jnp.asarray(alloc.page_table), layer)
    clean, _ = ops.flash_ft_decode(*args, ft=ft, interpret=True)
    spec = InjectionSpec(row=1, col=7, k_step=1, magnitude=777.0)
    g = 1 * kvh + 0                       # grid row: slot 1, kv head 0
    dirty, rep = ops.flash_ft_decode(*args, ft=ft, spec=spec, inj_g=g,
                                     interpret=True)
    rep = np.asarray(rep)
    assert rep[g, 0, 0] >= 1              # detected on the right grid row
    assert rep[g, 0, 2] == spec.row and rep[g, 0, 3] == spec.col
    assert abs(rep[g, 0, 4] - 777.0) < 1.0
    # off-row report rows stay silent
    assert float(np.delete(rep[..., 0], g, axis=0).sum()) == 0.0
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


def test_paged_decode_seu_detect_only_leaves_error():
    kvh, dh, page = 2, 256, 16
    q, cache, alloc = _exact_paged_kv([272, 320], kvh, dh, page)
    args = (q, cache["k_pages"], cache["v_pages"],
            jnp.asarray(alloc.lengths), jnp.asarray(alloc.page_table), 0)
    clean, _ = ops.flash_ft_decode(
        *args, ft=FTConfig(level="block", action="correct"), interpret=True)
    # inject at the LAST live kv step of slot 1 (len 320 → 20 pages) so the
    # online-softmax rescale can't annihilate the uncorrected SEU
    spec = InjectionSpec(row=1, col=7, k_step=320 // page - 1,
                         magnitude=777.0)
    g = 1 * kvh + 0
    dirty, rep = ops.flash_ft_decode(
        *args, ft=FTConfig(level="block", action="detect"), spec=spec,
        inj_g=g, interpret=True)
    assert np.asarray(rep)[g, 0, 0] >= 1
    diff = np.abs(np.asarray(clean) - np.asarray(dirty)).max()
    assert diff > 1.0, "detect-only must leave the fault in the output"


def test_flash_ft_decode_rejects_unaligned_head_dim():
    with pytest.raises(ValueError):
        ops.flash_ft_decode(jnp.zeros((1, 2, 64)),
                            jnp.zeros((1, 2, 1, 16, 64)),
                            jnp.zeros((1, 2, 1, 16, 64)),
                            jnp.zeros((1,), jnp.int32),
                            jnp.zeros((1, 1), jnp.int32), 0,
                            ft=FT_PALLAS)


@pytest.mark.parametrize("layer", [0, 2])
def test_paged_decode_reads_layer_of_stacked_pool(layer):
    """The kernel on layer ``layer`` of a 3-layer pool (a traced index, as
    the layer scan passes it) gives bitwise the output and report of the
    same call on that layer alone as a one-layer pool."""
    lengths, kvh, nrep, dh, page, mp = [17, 64, 0], 2, 2, 128, 16, 4
    rng = np.random.default_rng(layer)
    n_pages = 1 + len(lengths) * mp
    alloc = kvc.PageAllocator(n_pages, len(lengths), mp, page)
    for length in lengths:
        alloc.alloc_slot(length)
    shape = (3, n_pages, kvh, page, dh)
    k_all = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v_all = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q = jnp.asarray(rng.standard_normal((len(lengths), kvh * nrep, dh)),
                    jnp.float32)
    lens, table = jnp.asarray(alloc.lengths), jnp.asarray(alloc.page_table)
    ft = FTConfig(level="block", action="correct")
    out, rep = jax.jit(lambda l: ops.flash_ft_decode(
        q, k_all, v_all, lens, table, l, ft=ft, interpret=True))(layer)
    one, rep_one = ops.flash_ft_decode(q, k_all[layer][None],
                                       v_all[layer][None], lens, table, 0,
                                       ft=ft, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(one))
    np.testing.assert_array_equal(np.asarray(rep), np.asarray(rep_one))
    assert float(np.asarray(rep)[..., 0].sum()) == 0.0


# ---------------------------------------------------------------------------
# model: paged_decode_step ≡ dense decode_step + jaxpr audit
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def paged_vs_dense(tiny_params):
    """Build matching dense and paged caches (per-row lengths incl. a cold
    slot and a page-boundary length) and run one step of each path."""
    cfg = TINY
    b, page, mp = 3, 8, 4
    smax = page * mp
    lengths = [9, 24, 0]                  # 24 = 3 full pages exactly
    ctx = Ctx(ft=FT_PALLAS, dtype=jnp.float32, attn_shard="none")
    rng = np.random.default_rng(0)

    dense = tfm.init_cache(cfg, b, smax, jnp.float32)
    for slot, length in enumerate(lengths):
        if length == 0:
            continue
        toks = jnp.asarray(rng.integers(1, 200, (1, length)), jnp.int32)
        _, c1 = tfm.prefill(tiny_params, toks,
                            tfm.init_cache(cfg, 1, smax, jnp.float32),
                            cfg, ctx)
        dense["k"] = dense["k"].at[:, slot].set(c1["k"][:, 0])
        dense["v"] = dense["v"].at[:, slot].set(c1["v"][:, 0])
        dense["length"] = dense["length"].at[slot].set(length)

    n_pages = 1 + b * mp
    alloc = kvc.PageAllocator(n_pages, b, mp, page)
    paged = kvc.init_paged_cache(cfg.n_layers, n_pages, b, mp,
                                 cfg.n_kv_heads, page, cfg.head_dim,
                                 jnp.float32)
    for slot, length in enumerate(lengths):
        if length == 0:
            continue
        s, _ = alloc.alloc_slot(length)
        assert s == slot
        paged = kvc.write_prefill(paged, s,
                                  jnp.asarray(alloc.page_table[s]),
                                  dense["k"][:, slot, :length],
                                  dense["v"][:, slot, :length], length)
    # engine protocol: ensure() reserves *capacity* for the next token; the
    # device-visible length stays the decoded-so-far count
    s, _ = alloc.alloc_slot(0)
    for slot in range(b):
        alloc.ensure(slot, lengths[slot] + 1)
    paged["page_table"] = jnp.asarray(alloc.page_table)
    paged["length"] = jnp.asarray(lengths, jnp.int32)

    tok = jnp.asarray(rng.integers(1, 200, (b, 1)), jnp.int32)
    ld, cd = tfm.decode_step(tiny_params, tok, dense, cfg, ctx)
    lp, cp = tfm.paged_decode_step(tiny_params, tok, paged, cfg, ctx)
    return dict(cfg=cfg, ctx=ctx, lengths=lengths, tok=tok, paged=paged,
                ld=ld, cd=cd, lp=lp, cp=cp)


def test_paged_decode_step_matches_dense_logits(paged_vs_dense):
    err = np.abs(np.asarray(paged_vs_dense["ld"])
                 - np.asarray(paged_vs_dense["lp"])).max()
    assert err < 2e-4, err


def test_paged_decode_step_matches_dense_cache(paged_vs_dense):
    d = paged_vs_dense
    kd, vd = kvc.gather_dense(d["cp"])
    for slot, length in enumerate(d["lengths"]):
        np.testing.assert_allclose(
            np.asarray(kd[:, slot, :length + 1]),
            np.asarray(d["cd"]["k"][:, slot, :length + 1]), atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(vd[:, slot, :length + 1]),
            np.asarray(d["cd"]["v"][:, slot, :length + 1]), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(d["cp"]["length"]),
                                  np.asarray(d["paged"]["length"]) + 1)


def test_paged_decode_step_audit(paged_vs_dense, tiny_params):
    """The engine's decode step lowers with zero unprotected dot_generals
    and the paged flash decode kernel in the trace."""
    from repro.tools.audit import unprotected_dots, pallas_call_names
    d = paged_vs_dense
    fn = lambda p, t, c: tfm.paged_decode_step(p, t, c, d["cfg"],
                                               d["ctx"])[0]
    bad = unprotected_dots(fn, tiny_params, d["tok"], d["paged"])
    assert not bad, bad
    names = pallas_call_names(fn, tiny_params, d["tok"], d["paged"])
    assert any("flash_decode" in n for n in names), names


# ---------------------------------------------------------------------------
# engine: continuous batching conservation + telemetry attribution
# ---------------------------------------------------------------------------

_PROMPT_LENS = [5, 13, 9, 21]
_MAX_NEW = [6, 3, 8, 4]


@pytest.fixture(scope="module")
def engine_run(tiny_params):
    """One multi-slot engine run over 4 requests on 2 slots (forces
    queueing + slot reuse), plus per-request solo-greedy references."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 200, (length,)) for length in _PROMPT_LENS]
    run = RunConfig(model=TINY, ft=FT_PALLAS, dtype="float32")
    em = MemoryEmitter()
    sink = MetricsSink(emitters=[em])
    eng = ServeEngine(tiny_params, TINY, run,
                      EngineConfig(max_len=64, n_slots=2, page_size=8,
                                   max_new_tokens=8), sink=sink)
    for p, m in zip(prompts, _MAX_NEW):
        eng.submit(p, max_new_tokens=m)
    res = eng.run()
    solo = []
    for p, m in zip(prompts, _MAX_NEW):
        one = ServeEngine(tiny_params, TINY, run,
                          EngineConfig(max_len=64, n_slots=1, page_size=8))
        one.submit(p, max_new_tokens=m)
        solo.append(one.run()[0])
    return dict(prompts=prompts, eng=eng, res=res, solo=solo,
                records=em.records)


def test_engine_no_starvation(engine_run):
    """Every submitted request completes with exactly its token budget."""
    res = engine_run["res"]
    assert len(res) == len(_PROMPT_LENS)
    for i, r in enumerate(res):
        assert r.rid == i
        assert r.prompt_len == _PROMPT_LENS[i]
        assert len(r.tokens) == _MAX_NEW[i]
        assert r.ttft_s >= 0.0


def test_engine_conserves_solo_greedy_tokens(engine_run):
    """Continuous batching is invisible to outputs: each request decodes to
    exactly the tokens a dedicated single-slot engine produces."""
    for r, s in zip(engine_run["res"], engine_run["solo"]):
        assert r.tokens == s.tokens, (r.rid, r.tokens, s.tokens)


def test_engine_returns_all_pages(engine_run):
    eng = engine_run["eng"]
    assert eng.alloc.n_free == eng.plan.n_pages - 1
    eng.alloc.check_invariants()
    assert not eng.alloc.live.any()


def test_engine_telemetry_attributes_decode_sites(engine_run):
    """Sink records cover both phases; decode detections land on the
    `dec_flash` site; decoded-token and TTFT accounting is exact."""
    recs = engine_run["records"]
    phases = {r["gauges"].get("phase") for r in recs}
    assert phases == {"prefill", "decode"}
    dec = [r for r in recs if r["gauges"]["phase"] == "decode"]
    sites = {row["site"] for r in dec for row in r.get("ft_sites") or ()}
    assert "dec_flash" in sites, sites
    assert all(r["ft"]["detected"] == 0.0 for r in recs)  # clean run
    dec_toks = max(r["counters"].get("decoded_tokens", 0) for r in recs)
    assert dec_toks == sum(m - 1 for m in _MAX_NEW)   # 1st tok = prefill
    n_req = max(r["counters"].get("requests", 0) for r in recs)
    assert n_req == len(_PROMPT_LENS)
    assert any("ttft_s" in r.get("hists", {}) for r in recs)


def test_engine_rejects_bad_requests(tiny_params):
    run = RunConfig(model=TINY, ft=FT_PALLAS, dtype="float32")
    eng = ServeEngine(tiny_params, TINY, run,
                      EngineConfig(max_len=32, n_slots=1, page_size=8))
    with pytest.raises(ValueError):
        eng.submit(np.arange(1, 40), max_new_tokens=4)   # > max_len
    with pytest.raises(ValueError):
        eng.submit(np.asarray([], np.int64))             # empty prompt
    with pytest.raises(ValueError):
        eng.submit(np.asarray([1, 2]), max_new_tokens=0)


def test_engine_unsupported_family_raises(tiny_params):
    from repro.configs import registry
    cfg = registry.get_smoke("mamba2-780m")
    run = RunConfig(model=cfg, ft=FT_PALLAS, dtype="float32")
    with pytest.raises(NotImplementedError):
        ServeEngine(tiny_params, cfg, run, EngineConfig())


# ---------------------------------------------------------------------------
# engine: host spans at each layer boundary (tools/trace's ring)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("warm", [False, True])
def test_engine_spans_one_admission_and_one_decode_step(tiny_params, warm):
    """One admission and one decode step leave exactly the records of the
    engine's span table, each under its parent with its attributes; a
    prompt length no earlier step compiled shows as a ``jax/compile``
    record under ``serve/prefill``, a warmed one as none."""
    import time
    from repro.tools import trace
    run = RunConfig(model=TINY, ft=FT_PALLAS, dtype="float32")
    eng = ServeEngine(tiny_params, TINY, run,
                      EngineConfig(max_len=64, n_slots=2, page_size=8))
    prompt = np.arange(1, 12)
    if warm:
        eng.submit(prompt, max_new_tokens=2)
        eng.run()
    rid = eng.submit(prompt, max_new_tokens=3)
    t0 = time.perf_counter_ns()
    assert eng.step()
    recs = [r for r in trace.records() if r.end_ns >= t0]
    by_id = {r.id: r for r in recs}

    def parent(r):
        return by_id[r.parent].name if r.parent in by_id else None

    got = sorted((r.name, parent(r), r.rid, tuple(sorted(r.attrs.items())))
                 for r in recs if r.name.startswith("serve/"))
    free = eng.alloc.n_free
    want = sorted([
        ("serve/step", None, None, ()),
        ("serve/queued", "serve/step", rid, ()),
        ("serve/admit", "serve/step", rid, ()),
        ("serve/prefill", "serve/admit", rid, (("prompt_len", 11),)),
        ("serve/kv_write", "serve/admit", rid, ()),
        ("serve/sample", "serve/admit", rid, ()),
        ("serve/prepare", "serve/step", None, ()),
        ("serve/dispatch", "serve/step", None,
         (("free_pages", free), ("live", 1), ("n_slots", 2))),
        ("serve/sample", "serve/step", None, ()),
        ("serve/retire", "serve/step", None, (("finished", 0),)),
    ])
    assert got == want
    queued = next(r for r in recs if r.name == "serve/queued")
    admit = next(r for r in recs if r.name == "serve/admit")
    assert queued.start_ns <= t0 and queued.end_ns <= admit.start_ns
    compiles = [r for r in recs if r.name == "jax/compile"
                and parent(r) == "serve/prefill"]
    assert bool(compiles) == (not warm), compiles
    assert all(r.rid == rid for r in compiles)
