"""Pallas TPU kernels for the paper's compute hot-spot: GEMM.

Since PR 2 the kernel layer is a *generator*, not a collection of
hand-written bodies — the paper's template-based code generation (§3.2)
grown into a declarative pipeline:

    spec  →  template  →  autotune  →  launch

  1. **spec** (`templates/spec.py`) — a `KernelSpec` names one variant:
     FT level (off/inner/tile/block) × masked-vs-plain dispatch × an
     epilogue chain (bias-add, activation, residual-add from the
     `templates/epilogues.py` registry) × accumulate/output dtypes ×
     **extra outputs** (PR 4 — multi-output kernels: "act_grad" writes the
     derivative of the chain's nonlinear activation at the pre-activation
     as a second VMEM output, computed from the verified/corrected
     accumulator). A `BatchedKernelSpec` (PR 3/4) extends the space with a
     leading batch axis: uniform batched (B, M, K) × (B, K, N) (or a
     shared (K, N) right operand), CSR-style *grouped* dispatch
     (row-sorted token buffer + per-group B selected by a
     scalar-prefetched tile→group map, per-group checksums, ragged group
     edges masked in-kernel — zero capacity padding), and the **tgmm**
     variant — the grouped *transpose* GEMM dw[g] = X_gᵀ G_g of the MoE
     backward, output-stationary over (G, K, N).
  2. **template** (`templates/emit.py`) — `render(spec, …)` composes the
     staged emitter (prologue / K-loop MAC + running checksums / fused
     epilogue + writeback) into ONE Pallas kernel body; `render_tgmm` is
     the one structurally different body (its grid walks row tiles as the
     reduction axis; the accumulator + per-group checksums flush when the
     scalar-prefetched group id changes between consecutive tiles). Fused
     epilogues apply to the VMEM-resident accumulator before the single
     HBM writeback, with linear ops folded into the ABFT checksum
     comparison so detection/correction still works post-epilogue — and
     extra outputs are written from the *corrected* accumulator, so a
     forward SEU never reaches a saved residual.
  3. **autotune** (`autotune.py` + `search.py` + `tune_cache.py`) — the
     candidate search enumerates MXU-aligned tiles under the variant-aware
     VMEM model, now owned by the spec (`KernelSpec.vmem_bytes`): fused
     epilogues add aux-operand buffers, extra outputs add their (bm, bn)
     output block, and the tgmm variant swaps in its transposed geometry
     ((bm,bk)+(bm,bn) operand tiles, (bk,bn) accumulator, bk-row checksum
     scratch). `search.predicted_time_s` models each the same way (the
     tgmm branch streams X once per N-block column, G once per K-block
     row, writes dw once per group in f32, and charges the G·(bm-1)
     reduction-dim alignment rows). Cache keys include the variant
     (`KernelSpec.variant_key()` — e.g. ``/v_tgmm``, ``/v_xo_act_grad``)
     plus the pow2-bucketed ``/b_*``/``/g_*`` count component; existing
     keys are unchanged so older caches stay valid.
  4. **launch** (`templates/registry.py`, `ops.py`) — `ops.gemm_call(spec,
     a, b, …)` is the 2-D front door (multi-output specs return
     ((C, extra…), report)) and `ops.grouped_gemm_call` its
     batched/grouped sibling, rank-dispatching: 3-D a → uniform batched;
     2-D a + 3-D b + group_ids → grouped; 2-D a + 2-D b + group_ids +
     n_groups → tgmm. `ops.matmul` / `ops.ft_matmul_report` /
     `ops.fused_matmul(..., save_act_grad=True)` are thin specializations;
     `core.ft_batched_dot` / `core.ft_grouped_matmul` / `core.ft_dot_fused`
     are the policy-level fronts the model zoo calls — since PR 4 their
     custom_vjps keep the *backward* GEMMs on registry kernels too
     (dx/dw/dbuf on the 2-D/grouped kernels, the grouped dw on tgmm, and
     ft_dot_fused consuming the saved act_grad residual instead of
     recomputing the pre-activation GEMM).

Worked example — protecting an MoE expert FFN end to end, BOTH directions
(what `models/moe.py` + `core.ft_grouped_matmul` run)::

    import jax, jax.numpy as jnp
    from repro.core import ft_grouped_matmul
    from repro.core.policy import FTConfig

    # tokens (T, d) each routed to one of G experts; weights (G, d, f).
    ft = FTConfig(level="block", backend="pallas")
    loss = lambda w: jnp.sum(ft_grouped_matmul(tokens, w, expert_ids,
                                               ft=ft))
    dw = jax.grad(loss)(w_gate)
    # forward: the CSR-style grouped kernel (per-group checksums).
    # backward: d_buf reruns the grouped kernel on wᵀ; dw runs the
    # OUTPUT-STATIONARY TGMM KERNEL — grid walks the buffer's row tiles,
    # dw[g] accumulates in VMEM while tiles of group g stream by, and the
    # per-group checksums (col (X_g e)ᵀG_g, row X_gᵀ(G_g e)) verify and
    # branchlessly correct at the group-boundary flush. One SEU per
    # (group × output block) is corrected; empty groups return exact 0.

    # Tuning the tgmm variant explicitly:
    #   spec = templates.BatchedKernelSpec(ft_level="block", tgmm=True)
    #   autotune.best_params(T, f, d, 4, ft_level="block", spec=spec,
    #                        groups=G)      # cache key gains /v_tgmm/g_*
    # Multi-output fused forward (what ft_dot_fused's vjp uses):
    #   (y, actp), rep = ops.fused_matmul(x, w, bias=b, act="gelu",
    #                                     ft=ft, save_act_grad=True)
    # `benchmarks/backward_path.py` reports the fraction of train-step
    # GEMM FLOPs under in-kernel ABFT (and gates it ≥ 0.99 in CI).

Worked example — flash attention protected in BOTH directions (PR 5; what
`models.blocks.chunked_attention` runs on the pallas backend)::

    from repro.kernels import ops
    # forward: ONE launch; save_stats adds the per-row (m, l) softmax
    # statistics — the saved residual of the dedicated backward.
    out, m, l, rep = ops.flash_ft(q, k, v, ft=ft, causal=True,
                                  n_rep=n_rep, save_stats=True)
    # backward: TWO launches (dQ; dK/dV) — zero oracle recompute. The four
    # backward GEMMs (dP=g·Vᵀ, dV=Pᵀ·g, dQ=dS·K, dK=dSᵀ·Q) and the S
    # recompute all carry in-kernel checksums + branchless correction.
    dq, dk, dv, rep_dq, rep_dkv = ops.flash_ft_bwd(
        q, k, v, out, m, l, g, ft=ft, causal=True, n_rep=n_rep)

    # Tuning the flash variants explicitly — each direction owns a cache
    # key (existing keys unchanged):
    #   spec = templates.FlashKernelSpec(ft_level="block", direction="dq",
    #                                    dh=128)
    #   autotune.best_params(Sq, Skv, 128, 4, ft_level="block", spec=spec,
    #                        batch=B*H)    # key gains /v_flashbwd_dq/b_*
    # (bm, bn) come back as the (stationary, streamed) seq blocks; the
    # head dim never tiles (spec.dh, not bk).

    # Worked injection campaign — stochastic SEUs INSIDE the kernels (the
    # MPGemmFI lesson: the injector must live in the kernel it measures;
    # a campaign whose jaxpr falls back to a jnp oracle measures nothing):
    #   ftc = FTConfig(level="block", backend="pallas", inject_rate=1.0)
    #   out, rep = ops.flash_ft(q, k, v, ft=ftc, key=jax.random.PRNGKey(0))
    #   assert float(rep[..., 0].sum()) > 0          # detections happened
    #   # ... and per-GEMM deterministic SEUs for conformance tests:
    #   ops.flash_ft_bwd(..., inject=InjectionSpec(row=5, col=9,
    #                    magnitude=777.0, k_step=1), inj_target="dk",
    #                    inj_bh=1, inj_blk=1)
    # `tools.audit.pallas_call_names` asserts the campaign's jaxpr contains
    # the flash kernels (tests/test_flash_backward.py).

Worked example — per-site FT telemetry end to end (PR 8; the observability
layer over everything above)::

    from repro.core import telemetry
    from repro.models.blocks import Ctx
    from repro.tools import metrics

    # 1. Attribution: every Ctx-routed GEMM carries a structured site
    #    label ("wq", "moe_gate", "attn_flash", …); a trace-time registry
    #    maps labels to stable column ids of the report's fixed-width site
    #    matrices, and the layer scan places each layer's rows at
    #    1 + layer_idx (row 0 = unlayered). The SCALAR totals are reduced
    #    exactly as before PR 8 — sum(site_detected) == detected,
    #    bit-identical to the global triple.
    ctx = Ctx(ft=ftc, key=key, inject_sites=("moe_gate",))  # filtered SEUs
    loss, mets = mod.loss_fn(params, batch, cfg, ctx)
    telemetry.site_rows(mets["ft"])   # [{site, layer, detected, …}, …]

    # 2. Sink: one host-side step boundary; JSONL/stdout/in-memory
    #    emitters; the storm detector rides along.
    sink = metrics.MetricsSink([metrics.JsonlEmitter("metrics.jsonl")])
    sink.on_storm(lambda a: print("SDC storm:", a.site, a.rate))
    sink.record_ft(mets["ft"], step=step); sink.step_end(step, loss=loss)

    # Zero-cost claim: the site matrices ride the existing report pytree —
    # benchmarks/telemetry_overhead.py gates ZERO extra pallas launches vs
    # telemetry.site_attribution(False), and runs the single-site campaign
    # (detections attribute to exactly the injected site) in CI.
    # Spans: kernel dispatch fronts wear @traced("kernel/…") name scopes;
    # `python -m benchmarks.run --trace-dir d/` dumps a Perfetto trace.

Worked example — paged ragged flash decode (PR 9; what the serving
engine's `transformer.paged_decode_step` launches per layer)::

    from repro.kernels import ops
    from repro.train import kv_cache as kvc

    # KV lives in a stacked page pool (n_layers, n_pages, KVH, page, dh)
    # — ONE page is ONE kv block of the kernel, streamed through a
    # scalar-prefetched page table and layer index, so the pool is read in
    # place; lengths int32[B] are per-row ragged (a slot at 17 tokens and
    # a slot at 4096 share the launch, each masked at ITS length; dead
    # slots ride the reserved null page and write exact zeros).
    out, rep = ops.flash_ft_decode(q, k_pages, v_pages, lengths,
                                   page_table, layer, ft=ft)
    # q (B, H, dh) with GQA folded to grid rows g = slot * KVH + kv_head
    # (n_rep query heads per row — KV never repeat-materialized); rep
    # (B*KVH, 1, 8) carries [det, corr, row, col, mag, max_res, tau, k].

    # Tuning the decode variant — its streamed block IS the page size, so
    # the autotuned bn feeds kv_cache.plan_pages and the cache layout and
    # the kernel tile stay ONE number:
    #   spec = templates.FlashKernelSpec(ft_level="block",
    #                                    direction="decode", dh=128)
    #   p = autotune.best_params(bq, max_len, 128, 4, ft_level="block",
    #                            spec=spec, batch=B*KVH)
    #   plan = kvc.plan_pages(cfg, ft, n_slots=B, max_len=max_len)
    #   assert plan.page_size == p.bn     # gather granularity ≡ kv block
    # (bq is the sublane-padded n_rep — decode's stationary axis is the
    # GQA group, not a seq block; the head dim never tiles.)
    # Deterministic SEUs address a grid row: ops.flash_ft_decode(...,
    # spec=InjectionSpec(row=1, col=7, k_step=1, magnitude=777.0),
    # inj_g=slot * KVH + kv_head); correction is bit-exact (the PV
    # accumulator is verified before the output rescale) —
    # tests/test_serve_engine.py gates this on every PR.

Worked example — per-site adaptive FT policy (PR 10; how a mixed-level
campaign picks WHICH kernels pay for protection)::

    from repro.core import policy
    from repro.core.policy import FTPolicy, ONLINE_BLOCK, OFFLINE_DETECT

    # 1. A policy is ordered (site-glob → FTConfig) rules + a default;
    #    every dispatch front above resolves its own `site=` label, so a
    #    single Ctx.ft drives different kernel variants per call site.
    pol = FTPolicy(rules=(("moe_*", ONLINE_BLOCK),
                          ("attn_*", OFFLINE_DETECT.replace(verify="final"))),
                   default=ONLINE_BLOCK)
    ctx = Ctx(ft=pol, key=key)        # a bare FTConfig still works: a
                                      # uniform policy is bit-identical,
                                      # tune-cache keys included.

    # 2. The static planner prices each site on the SAME roofline model
    #    the autotuner scores tiles with (`search.ft_plan_cost`):
    #    memory-bound sites absorb checksum FLOPs inside the bandwidth
    #    bound for free; compute-bound projections pay ~2K/(M·N) extra.
    with policy.record_site_costs() as costs:     # jax.eval_shape — no
        jax.eval_shape(loss_fn, params, batch)    # compute, full size OK
    plan = policy.plan_ft(costs.values(), budget_frac=0.01)
    print(plan.coverage, plan.overhead_frac)      # e.g. 1.00, 0.003
    ctx = Ctx(ft=plan.policy, key=key)

    # 3. The runtime loop closure: a StormDetector alert PROMOTES the
    #    storming site (detect→correct, final→step) for a cool-down
    #    window; current_policy() is a fresh frozen policy, so the jitted
    #    step retraces exactly when the resolved level changes.
    esc = policy.EscalationController(plan.policy, cooldown_steps=64)
    esc.attach(sink)                  # MetricsSink.on_storm / StormDetector
    loss = train_step(params, batch, esc.current_policy()); esc.step_end(s)

    # Since PR 10 the in-kernel stochastic SEU hook covers the ENTIRE
    # template family — 2-D, batched, grouped, and tgmm bodies, not just
    # flash — so whole-model campaigns on the pallas backend run with
    # zero jnp-injector call sites: pass key= to any front above with
    # ft.inject_rate > 0 (rate 0 with a key stays bit-identical).
    # `benchmarks/ft_plan.py` prints the coverage-vs-overhead Pareto
    # curve and gates planned < uniform-correct at ≥95% coverage in CI;
    # render a dumped plan with
    # `python -m repro.tools.report --policy benchmarks/ft_plan_moe.json`.

The epilogue extension hook is unchanged (register an `EpilogueOp` — give
it a ``grad`` rule and it can also ride the act_grad multi-output variant
— see `templates/epilogues.py`); batched/grouped specs accept aux-free
chains (activations); tgmm is epilogue-free.

Other modules:

  gemm.py     -- plain/masked non-FT entries + the naive ladder rung (§3)
  ftgemm.py   -- fused online-ABFT GEMM entry, 3 granularities (§4)
  flashft.py  -- flash attention with fused ABFT + ragged seq masking
                 (causal∧kv-edge mask on true lengths — ragged cross-length
                 causal runs on fitted blocks, no padded fallback) + GQA
                 via K/V index maps (n_rep — KV never repeat-materialized);
                 since PR 4 this is the training attention core on the
                 pallas backend (`models.blocks.chunked_attention`), and
                 since PR 5 its BACKWARD is first-class too: saved (m, l)
                 statistics, dedicated dQ/dK/dV kernels, degenerate-row
                 zeroing, and the in-kernel stochastic SEU hook
                 (`templates.emit.stochastic_seu`) for fault campaigns;
                 since PR 9 the paged DECODE direction: one query row per
                 GQA group, KV streamed page-by-page through a
                 scalar-prefetched page table with per-slot ragged lengths
                 (the serving engine's per-layer attention launch)
  grouped/    -- batched & grouped subsystem (layout + dispatch, PR 3;
                 tgmm backward-dw kernel, PR 4)
  ops.py      -- dispatching front doors (padding, autotune, interpret)
  ref.py      -- pure-jnp oracles (incl. the unfused epilogue composition)

Kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling). They are
validated with interpret=True on CPU, compiled for a described TPU v5e by
tests/test_tpu_compile.py, and run compiled on the chip by chip_smoke.py.
"""
from . import autotune, grouped, ops, ref, templates

__all__ = ["autotune", "grouped", "ops", "ref", "templates"]
