import os
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS", ""), os.environ.get("REPRO_XLA_EXTRA", ""),
    "--xla_force_host_platform_device_count=" +
    os.environ.get("REPRO_DRYRUN_DEVICES", "8"))))

"""ACTUALLY EXECUTE the sharded delay-adaptive train/serve step on a small
host-device mesh (default 8 CPU devices) -- the dry-run proves lowering; this
proves the distributed program runs: real sharded params, real collectives
(emulated on host), real delay-adaptive updates.

    PYTHONPATH=src python -m repro.launch.run_distributed --arch qwen2-moe-a2.7b \
        --reduced --steps 3 --mesh 2x4
"""
import argparse          # noqa: E402
import time              # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCH_IDS, get_config  # noqa: E402
from repro.data import EmbedStream, TokenStream  # noqa: E402
from repro.mesh import dp_size, make_mesh  # noqa: E402
from repro.mesh import batch_shardings, param_shardings  # noqa: E402
from repro.launch.steps import make_trainer  # noqa: E402
from repro.launch.train import PRESETS  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    g = ap.add_mutually_exclusive_group(required=True)
    g.add_argument("--arch", choices=ARCH_IDS)
    g.add_argument("--preset", choices=list(PRESETS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--mesh", default="2x4")
    args = ap.parse_args()

    cfg = (PRESETS[args.preset] if args.preset else get_config(args.arch))
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_mesh(tuple(int(x) for x in args.mesh.split("x")))
    n_workers = dp_size(mesh)
    trainer = make_trainer(cfg, n_workers=n_workers, lr=1e-3)

    with mesh:
        state = trainer.init(jax.random.PRNGKey(0))
        p_sh = param_shardings(state.params, mesh)
        state = state._replace(
            params=jax.device_put(state.params, p_sh),
            opt=state.opt._replace(
                inner=jax.device_put(
                    state.opt.inner,
                    param_shardings(state.opt.inner, mesh))))
        if cfg.embed_inputs:
            stream = EmbedStream(d_model=cfg.d_model, vocab=cfg.vocab,
                                 batch=args.batch, seq=args.seq,
                                 mrope=cfg.rope == "mrope")
        else:
            stream = TokenStream(vocab=cfg.vocab, batch=args.batch,
                                 seq=args.seq)
        step = jax.jit(trainer.train_step)
        for k in range(args.steps):
            batch = stream.batch_at(k)
            batch = jax.device_put(batch,
                                   batch_shardings(batch, mesh, args.batch))
            t0 = time.perf_counter()
            state, metrics = step(state, batch, jnp.int32(k % n_workers))
            loss = float(metrics["loss"])
            assert loss == loss, "NaN loss in distributed execution"
            print(f"step {k} loss {loss:.4f} gamma "
                  f"{float(metrics['gamma']):.2e} tau {int(metrics['tau'])} "
                  f"({time.perf_counter() - t0:.2f}s) "
                  f"devices={len(jax.devices())}")
    # param shards really live on distinct devices
    leaf = jax.tree_util.tree_leaves(state.params)[0]
    print(f"sharding of first param: {leaf.sharding}")
    print("DISTRIBUTED_RUN_OK")


if __name__ == "__main__":
    main()
