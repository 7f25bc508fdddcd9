"""End-to-end driver: asynchronous FL pre-training of a ~100M-class LM.

Trains a reduced 4-layer gemma-family decoder (same code path as the
production configs; see --full for the real sizes, which need the TPU
mesh of launch/dryrun.py) through the full async protocol for a few
hundred local steps, with round-growing sample sizes.

``--engine cohort|device`` runs the same task through the batched cohort
engines via the flat-params adapter (``repro.cohort.flat``) — the
whole population advances as one vmapped [C, D] block, which is the path
that scales past a handful of clients.  Batches are seed-addressed
((client, round, iteration) via ``fold_in``), so all engines follow the
same data order.

    PYTHONPATH=src python examples/llm_fl_pretrain.py [--rounds 8]
    PYTHONPATH=src python examples/llm_fl_pretrain.py --engine device
"""
import sys, os, argparse, time
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.cohort import make_simulator
from repro.core import BatchModelTask, round_stepsizes
from repro.configs import get_config, reduced
from repro.configs.base import StepSizeConfig
from repro.data import SeedAddressedBatcher
from repro.models import init_params, train_loss


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--clients", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--engine", default="event",
                    choices=["event", "cohort", "device"])
    args = ap.parse_args(argv)

    cfg = reduced(get_config(args.arch), n_layers=args.layers,
                  d_model=args.d_model, vocab=2048)
    n_params = cfg.param_count()
    print(f"{cfg.arch_id} reduced: {cfg.n_layers}L d={cfg.d_model} "
          f"~{n_params/1e6:.1f}M params")

    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    batcher = SeedAddressedBatcher(cfg, batch_size=args.batch,
                                   seq_len=args.seq, seed=0)
    task = BatchModelTask(cfg, params, batcher)

    # growing rounds: 1, 2, 3, ... local batch-steps per round
    sizes = [[1 + i for i in range(args.rounds)]] * args.clients
    etas = round_stepsizes(
        StepSizeConfig(kind="inv_sqrt", eta0=0.1, beta=0.05),
        sizes[0])

    loss0 = float(train_loss(cfg, params, batcher(0, 0, 0)))
    t0 = time.time()
    sim = make_simulator(args.engine, task, n_clients=args.clients,
                         sizes_per_client=sizes,
                         round_stepsizes=etas, d=1, seed=0,
                         speeds=[1.0 + 0.2 * c
                                 for c in range(args.clients)])
    res = sim.run(max_rounds=args.rounds)
    loss1 = float(train_loss(cfg, res["model"], batcher(0, 0, 0)))
    steps = sum(sizes[0]) * args.clients
    print(f"async FL [{args.engine}]: "
          f"{res['final']['round']} rounds, {steps} local steps, "
          f"{res['final']['messages']} messages, "
          f"wall {time.time()-t0:.1f}s")
    print(f"eval loss {loss0:.3f} -> {loss1:.3f}")
    assert loss1 < loss0, "loss should decrease"
    return {"loss0": loss0, "loss1": loss1, "result": res, "sim": sim}


if __name__ == "__main__":
    main()
