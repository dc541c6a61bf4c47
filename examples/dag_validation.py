"""§V-D of the paper, against *this machine*: predict the iteration
time of a real multi-device data-parallel training run from its own
measured layer costs via the DAG model, then compare with the measured
wall-clock — the exact Fig. 4 methodology (paper reports 4.6-9.4%
error on Caffe-MPI; we run the same loop on forced host devices).

Forces 8 host CPU devices (and the CPU platform) before jax
initializes, so plain `python examples/dag_validation.py` works from a
normal single-device environment and never takes a host's chip.
"""
import json
import os
import sys
from pathlib import Path

N_DEV = 8


def child():
    import time

    import jax
    import jax.numpy as jnp

    from repro.comm.ddp import make_ddp_train_step
    from repro.configs import get_config
    from repro.core.analytical import eq5_wfbp
    from repro.core.dag import IterationCosts, build_ssgd_dag
    from repro.core.policies import CAFFE_MPI, CNTK
    from repro.core.simulator import simulate
    from repro.launch.mesh import make_dp_mesh
    from repro.models import transformer as T
    from repro.optim.sgd import sgd
    from repro.traces.generate import TimedLayer, generate_trace

    cfg = get_config("qwen1.5-4b").reduced(num_layers=4, d_model=128,
                                           num_heads=4, d_ff=256,
                                           vocab_size=1024)
    mesh = make_dp_mesh(N_DEV)
    key = jax.random.PRNGKey(0)
    params = T.init_lm(cfg, key)
    opt = sgd(lr=1e-2, momentum=0.9)
    B, S = 32, 64

    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}

    # --- 1. measure per-layer costs on ONE device (the paper measures
    # per-layer cuDNN times from Caffe) --------------------------------
    local_B = B // N_DEV
    x_tok = batch["tokens"][:local_B]
    emb_layer = TimedLayer("embed",
                           lambda p, t: p[t], params["embedding"])
    unit_layers = []
    p_units = params["units"]

    def block_apply(i):
        def apply(p, x):
            from repro.models import blocks as BL
            positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
            y, _ = BL.apply_block(cfg, cfg.layer_pattern[0], p, x, positions)
            return y
        return apply

    for u in range(cfg.num_units):
        unit_p = jax.tree_util.tree_map(lambda a: a[u], p_units)
        unit_layers.append(TimedLayer(f"layer{u}", block_apply(u),
                                      unit_p["b0"]))

    head_layer = TimedLayer(
        "head", lambda p, x: jnp.einsum("bsd,dv->bsv", x, p),
        params["lm_head"])
    labels_loc = batch["labels"][:local_B]

    def xent(p, logits):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        picked = jnp.take_along_axis(logp, labels_loc[..., None], -1)
        return -jnp.mean(picked) + 0.0 * jnp.sum(p)

    loss_layer = TimedLayer("loss", xent, jnp.zeros((1,)))

    trace = generate_trace([emb_layer] + unit_layers + [head_layer,
                                                        loss_layer],
                           x_tok, cfg.name, n_iterations=2, repeats=3)
    mean = trace.mean_iteration()

    # measure the optimizer update itself
    st0 = opt.init(params)
    g0 = jax.tree_util.tree_map(jnp.ones_like, params)
    upd = jax.jit(lambda g, s, p: opt.update(g, s, p))
    jax.block_until_ready(upd(g0, st0, params))
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(upd(g0, st0, params))
    t_u_measured = (time.perf_counter() - t0) / 5

    # comm cost per layer: measure one psum of that many bytes
    from jax.sharding import PartitionSpec as P

    def time_psum(nbytes):
        n = max(int(nbytes) // 4, 1)
        arr = jnp.ones((N_DEV, n), jnp.float32)
        f = jax.jit(jax.shard_map(lambda x: jax.lax.pmean(x, "data"),
                                  mesh=mesh, in_specs=P("data"),
                                  out_specs=P("data"), check_vma=False))
        jax.block_until_ready(f(arr))
        t0 = time.perf_counter()
        for _ in range(5):
            jax.block_until_ready(f(arr))
        return (time.perf_counter() - t0) / 5

    costs = IterationCosts(
        t_f=[r.forward_us * 1e-6 for r in mean],
        t_b=[r.backward_us * 1e-6 for r in mean],
        t_c=[time_psum(r.size_bytes) if r.size_bytes else 0.0 for r in mean],
        t_io=0.0, t_h2d=0.0, t_u=t_u_measured)

    # --- 2. DAG prediction -------------------------------------------
    # The N forced host devices share ONE physical core, so the DAG
    # must model worker compute on a shared channel (oversubscription);
    # the ideal-parallel prediction is reported alongside.
    pred = {}
    for pol in (CAFFE_MPI, CNTK):
        g = build_ssgd_dag(costs, N_DEV, pol, n_iterations=5,
                           shared_compute=True)
        pred[pol.name] = simulate(g).steady_iteration_time()
        g_ideal = build_ssgd_dag(costs, N_DEV, pol, n_iterations=5)
        pred[pol.name + "_ideal_parallel"] = \
            simulate(g_ideal).steady_iteration_time()
    pred["eq5"] = eq5_wfbp(costs)

    # --- 3. measured wall-clock of the real DDP step ------------------
    measured = {}
    for polname in ("wfbp", "at_end"):
        p0 = jax.tree_util.tree_map(lambda x: x.copy(), params)
        st = opt.init(p0)
        step = make_ddp_train_step(cfg, opt, mesh, sync_policy=polname)
        p0, st, m = step(p0, st, batch)          # compile
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        iters = 10
        for _ in range(iters):
            p0, st, m = step(p0, st, batch)
        jax.block_until_ready(m["loss"])
        measured[polname] = (time.perf_counter() - t0) / iters

    err = abs(pred["caffe-mpi"] - measured["wfbp"]) / measured["wfbp"] * 100
    out = {
        "predicted_wfbp_s": pred["caffe-mpi"],
        "predicted_cntk_s": pred["cntk"],
        "predicted_wfbp_ideal_parallel_s": pred["caffe-mpi_ideal_parallel"],
        "eq5_ideal_s": pred["eq5"],
        "measured_wfbp_s": measured["wfbp"],
        "measured_at_end_s": measured["at_end"],
        "prediction_error_pct": err,
        "paper_reported_error_pct": "4.6-9.4 (Caffe-MPI, Fig. 4)",
        "note": "N host devices share one physical core, so the DAG "
                "models worker compute on a shared channel",
    }
    print("RESULT " + json.dumps(out, indent=2))


def main():
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
    from repro.launch.hostdev import force_host_device_count

    force_host_device_count(N_DEV)
    os.environ["JAX_PLATFORMS"] = "cpu"
    child()


if __name__ == "__main__":
    main()
