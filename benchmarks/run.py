"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Paper mapping:

- fig11_d1_versions     : Basic vs Anticipation vs budget sweep for D1
- fig12_step_breakdown  : per-stage DDMS times (order/gradient/extract/
                          D0/D2/D1), strong-scaling shape
- fig13_strong_scaling  : DDMS blocks 1..8, fixed size (efficiency)
- fig13_weak_scaling    : size grows with block count
- fig14_dms_vs_ddms     : single-node DMS vs DDMS(4 blocks)
- fig15_vs_dipha        : DDMS vs boundary-matrix reduction (the DIPHA
                          algorithm core, clearing-optimized)
- gradient_throughput   : lower-star gradient vertices/s (jnp jit + Pallas)
- batched_serving       : PersistencePipeline.diagrams + TopoService
                          batch amortization vs per-field calls
- lm_train_step         : smoke-model tokens/s (framework side)

Everything topological runs through the ``PersistencePipeline`` facade
(``repro.pipeline``); per-stage timings come from its ``StageReport``.
``--quick`` runs a CPU-seconds subset for CI smoke.

Sizes are scaled to CPU-minutes; the ratios (speedups, efficiencies,
round counts) are the observables the paper's figures report.  The 512-chip
numbers live in EXPERIMENTS.md §Dry-run/§Roofline (compiled artifacts, not
wall clock).
"""

import argparse
import time
from pathlib import Path

import numpy as np

from repro.core.grid import Grid, vertex_order
from repro.core.reduction import compute_oracle
from repro.fields import make_field
from repro.pipeline import PersistencePipeline


def _row(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}", flush=True)


def _time(fn, reps=1):
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn()
    return (time.perf_counter() - t0) / reps * 1e6, out


DIMS = (16, 16, 16)
QUICK_DIMS = (8, 8, 8)


def _ddms(backend="jax", n_blocks=4, **kw):
    return PersistencePipeline(backend=backend, n_blocks=n_blocks,
                               distributed=True, **kw)


def fig11_d1_versions(dims=DIMS):
    f = make_field("backpack", dims, seed=1)
    g = Grid.of(*dims)
    for name, kw in [("basic", dict(anticipation=False)),
                     ("anticipation_b1", dict(budget=1)),
                     ("anticipation_b16", dict(budget=16)),
                     ("anticipation_auto", dict())]:
        pipe = _ddms(**kw)
        us, res = _time(lambda pipe=pipe: pipe.diagram(f, grid=g))
        st = res.stats
        _row(f"fig11_{name}", us,
             f"d1_rounds={st.get('d1_rounds')};"
             f"token_hops={st.get('d1_token_hops')};"
             f"expansions={st.get('d1_expansions')}")


def fig12_step_breakdown(dims=DIMS):
    f = make_field("backpack", dims, seed=1)
    g = Grid.of(*dims)
    res = _ddms().diagram(f, grid=g)
    stages = ("order", "gradient", "extract_sort", "d0", "d_top", "d1")
    tot = sum(res.stats[k] for k in stages)
    for k in stages:
        _row(f"fig12_{k}", res.stats[k] * 1e6,
             f"frac={res.stats[k] / tot:.2f}")


def fig13_strong_scaling(dims=DIMS):
    f = make_field("wavelet", dims, seed=2)
    g = Grid.of(*dims)
    base = None
    for nb in (1, 2, 4, 8):
        pipe = _ddms(n_blocks=nb)
        us, res = _time(lambda pipe=pipe: pipe.diagram(f, grid=g))
        base = base or us
        _row(f"fig13_strong_nb{nb}", us,
             f"rel={base / us:.2f};d1_rounds={res.stats.get('d1_rounds')}")


def fig13_weak_scaling():
    for nb, nz in ((1, 8), (2, 16), (4, 32)):
        dims = (12, 12, nz)
        f = make_field("magnetic", dims, seed=3)
        g = Grid.of(*dims)
        pipe = _ddms(n_blocks=nb)
        us, res = _time(lambda g=g, f=f, pipe=pipe: pipe.diagram(f, grid=g))
        _row(f"fig13_weak_nb{nb}", us,
             f"nv={g.nv};ncrit={res.stats['n_critical']}")


def fig14_dms_vs_ddms(dims=DIMS):
    dms = PersistencePipeline(backend="jax", distributed=False)
    ddms = _ddms()
    for name in ("wavelet", "random", "isabel"):
        f = make_field(name, dims, seed=4)
        g = Grid.of(*dims)
        us_dms, _ = _time(lambda f=f, g=g: dms.diagram(f, grid=g))
        us_ddms, _ = _time(lambda f=f, g=g: ddms.diagram(f, grid=g))
        _row(f"fig14_{name}", us_ddms,
             f"dms_us={us_dms:.0f};overhead={us_ddms / us_dms:.2f}")


def fig15_vs_dipha():
    dims = (8, 8, 8)  # reduction is the bottleneck; the point is the gap
    ddms = _ddms()
    for name in ("wavelet", "random"):
        f = make_field(name, dims, seed=5)
        g = Grid.of(*dims)
        us_red, _ = _time(lambda f=f, g=g: compute_oracle(g, f, twist=True))
        us_ddms, _ = _time(lambda f=f, g=g: ddms.diagram(f, grid=g))
        _row(f"fig15_{name}", us_ddms,
             f"dipha_like_us={us_red:.0f};speedup={us_red / us_ddms:.1f}x")


def gradient_throughput(quick=False):
    """vertices/s + modeled HBM bytes/vertex, pre-pass vs fused paths."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.kernels.ops import gradient_hbm_model

    def bench(dims, backend, reps=3, label=None):
        g = Grid.of(*dims)
        f = make_field("random", dims, seed=6)
        o = jnp.asarray(np.asarray(vertex_order(f.astype(np.float64))))

        def fn():
            return jax.block_until_ready(
                ops.lower_star_gradient(g, o, backend=backend))

        fn()  # compile
        us, _ = _time(fn, reps=reps)
        model = gradient_hbm_model(dims)
        kind = "prepass" if backend == "pallas_prepass" else "fused"
        tag = label or f"{backend}_{'x'.join(map(str, dims))}"
        _row(f"gradient_{tag}", us,
             f"vertices_per_s={g.nv / (us / 1e6):.0f};"
             f"model_bytes_per_vertex={model[kind]:.1f};path={kind}")

    # the jax backend fuses the gather into one jit program (fused model)
    for dims in ((16, 16, 16),) if quick else ((16, 16, 16), (32, 32, 32)):
        bench(dims, "jax", label=f"jax_{dims[0]}cubed")
    # Pallas kernels run in interpret mode on CPU: wall time is dominated
    # by the interpreter, so keep the grid small — the bytes/vertex model
    # is the hardware-relevant observable
    dims_p = (8, 8, 8) if quick else (16, 16, 8)
    bench(dims_p, "pallas", reps=1,
          label=f"pallas_fused_interp_{'x'.join(map(str, dims_p))}")
    bench(dims_p, "pallas_prepass", reps=1,
          label=f"pallas_prepass_interp_{'x'.join(map(str, dims_p))}")


def batched_serving(dims=(8, 8, 8), batch=6):
    """Batched diagrams() + TopoService vs one-at-a-time calls."""
    from repro.serve import TopoService
    g = Grid.of(*dims)
    fields = [make_field("random", dims, seed=s) for s in range(batch)]
    pipe = PersistencePipeline(backend="jax")
    pipe.diagram(fields[0], grid=g)  # compile the single path
    us_one, _ = _time(lambda: [pipe.diagram(f, grid=g) for f in fields])
    pipe.diagrams(fields, grid=g)    # compile the batched path
    us_bat, _ = _time(lambda: pipe.diagrams(fields, grid=g))
    _row(f"batched_diagrams_b{batch}", us_bat,
         f"sequential_us={us_one:.0f};speedup={us_one / us_bat:.2f}x")
    with TopoService(pipeline=pipe, max_batch=batch,
                     max_wait_s=0.05) as svc:
        us_svc, _ = _time(lambda: svc.map(fields, grid=g))
        st = svc.stats.as_dict()
    _row(f"topo_service_b{batch}", us_svc,
         f"batches={st['batches']};max_batch={st['max_batch']}")


def lm_train_step():
    import jax
    from repro.configs import smoke_config
    from repro.data.pipeline import DataConfig, batch_at
    from repro.models import transformer as T
    from repro.train.optimizer import OptConfig, init_opt_state
    from repro.train.train_step import StepConfig, make_train_step
    cfg = smoke_config("minitron-4b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    dc = DataConfig(cfg.vocab, batch=8, seq=64)
    step = jax.jit(make_train_step(cfg, OptConfig(),
                                   StepConfig(remat=False)))
    b = batch_at(dc, 0)
    params, opt, _ = step(params, opt, b)  # compile
    us, _ = _time(lambda: jax.block_until_ready(
        step(params, opt, batch_at(dc, 1))[2]["loss"]), reps=3)
    _row("lm_train_step_smoke", us,
         f"tokens_per_s={8 * 64 / (us / 1e6):.0f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small-size subset for CI smoke")
    args = ap.parse_args()
    from repro.compile_cache import use_checkout_cache
    use_checkout_cache(Path(__file__).resolve().parents[1])
    print("name,us_per_call,derived")
    if args.quick:
        fig12_step_breakdown(QUICK_DIMS)
        fig14_dms_vs_ddms(QUICK_DIMS)
        gradient_throughput(quick=True)
        batched_serving(dims=(6, 6, 6), batch=4)
        return
    fig11_d1_versions()
    fig12_step_breakdown()
    fig13_strong_scaling()
    fig13_weak_scaling()
    fig14_dms_vs_ddms()
    fig15_vs_dipha()
    gradient_throughput()
    batched_serving()
    lm_train_step()


if __name__ == "__main__":
    main()
