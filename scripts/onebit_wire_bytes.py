"""Bytes-on-wire evidence for 1-bit Adam AND 1-bit LAMB (reference claim:
~5x end-to-end comm reduction from 1-bit momentum exchange, deepspeed
0.3.15 onebit blog; the 20B north-star config names 1-bit LAMB).

Compiles the SAME data-parallel train step (GPT on a dp8 mesh) in the
warmup phase (fp32 gradient pmean) and the compressed phase (1-bit
two-phase momentum exchange, runtime/comm/onebit_spmd.py), audits every
collective's result bytes in the compiled HLO, and writes the
git-ignored ONEBIT_WIRE.json with the measured reduction factor. Runs on the virtual
CPU mesh — the compiled program, not hardware, is the evidence.

Scales: the default audits BOTH the tiny smoke model and GPT-125M
(--models tiny,125m) — the 125M entry is the model-scale evidence
(VERDICT r3 weak #6: bucket geometry and the (W, n) error-feedback
buffers only stress the design at real model sizes).

Usage: run under the cleaned 8-device env (see tests/conftest.py), or let
it re-exec itself.  ``scripts/comm_bench.py --onebit`` (the gradient-side
wire bench for the "comm" config block) delegates here.
"""

import json
import os
import subprocess
import sys

REEXEC_FLAG = "DS_ONEBIT_WIRE_REEXEC"


def main():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax

    if len(jax.devices()) < 8 and not os.environ.get(REEXEC_FLAG):
        env = dict(os.environ)
        env[REEXEC_FLAG] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
        sys.exit(subprocess.call(
            [sys.executable, os.path.abspath(__file__)] + sys.argv[1:],
            env=env))

    import numpy as np

    from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt
    from deeperspeed_tpu.parallel import build_mesh
    from deeperspeed_tpu.profiling.hlo_bytes import compiled_wire_bytes
    from deeperspeed_tpu.runtime.comm.onebit import OnebitAdam, OnebitLamb
    from deeperspeed_tpu.runtime.comm.onebit_spmd import (
        make_onebit_lamb_spmd_train_step, make_onebit_spmd_train_step)

    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="tiny,125m")
    ap.add_argument("--optimizers", default="adam,lamb")
    args = ap.parse_args()

    mesh = build_mesh({"data": 8})
    CFGS = {
        "tiny": GPTConfig(vocab_size=512, n_layer=2, n_head=4, d_model=128,
                          max_seq=64, attn_impl="xla", remat=True),
        # GPT-125M: the model-scale wire evidence (n ~ 124M params; the
        # (8, n) worker error buffer is ~4GB fp32 sharded over the mesh)
        "125m": GPTConfig(vocab_size=50304, n_layer=12, n_head=12,
                          d_model=768, max_seq=64, attn_impl="xla",
                          remat=True),
    }
    MAKERS = {"adam": (OnebitAdam, make_onebit_spmd_train_step),
              "lamb": (OnebitLamb, make_onebit_lamb_spmd_train_step)}

    out_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "ONEBIT_WIRE.json")
    result = {"mesh": "dp8"}
    if os.path.isfile(out_path):  # merge partial reruns
        try:
            with open(out_path) as f:
                prev = json.load(f)
        except (OSError, ValueError):
            prev = {}
        keep = {"mesh", "adam", "lamb", "adam_125m", "lamb_125m"}
        result.update({k: v for k, v in prev.items() if k in keep})

    for model in [m.strip() for m in args.models.split(",")]:
        cfg = CFGS[model]
        init_fn, _, loss_fn, _ = make_gpt(cfg)
        params = init_fn(jax.random.PRNGKey(0))
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(params))
        batch = np.zeros((8, cfg.max_seq // 2 + 1), np.int32)
        for opt_name in [o.strip() for o in args.optimizers.split(",")]:
            opt_cls, maker = MAKERS[opt_name]
            opt = opt_cls(lr=1e-3, freeze_step=2)
            entry = {"n_params": n_params}
            for phase in ("warmup", "compressed"):
                init_comm, step = maker(loss_fn, opt, mesh, phase=phase)
                comm = init_comm(params)
                bytes_by_op = compiled_wire_bytes(step, params, comm, batch,
                                                  1e-3, 3, world=8)
                entry[phase] = bytes_by_op
                # correctness: the compiled program must actually run
                p2, comm, loss = step(params, comm, batch, 1e-3, 3)
                entry[phase]["loss_ok"] = bool(np.isfinite(float(loss)))
                del p2, comm
            # wire_total models per-device link cost (ring all-reduce =
            # 2(W-1)/W x result; gathers/a2a = (W-1)/W) — the reference's
            # 1-bit claim is about exactly this physical traffic. The loss
            # pmean's tiny f32[] all-reduce rides along in both phases.
            entry["reduction_x"] = round(
                entry["warmup"]["wire_total"]
                / max(entry["compressed"]["wire_total"], 1), 1)
            key = opt_name if model == "tiny" else f"{opt_name}_{model}"
            result[key] = entry
            print(key, json.dumps(entry), flush=True)
            # write after EVERY entry: the XLA CPU collectives runtime can
            # abort at teardown (rendezvous timeout) after all results are
            # in — an end-of-run write would lose them
            with open(out_path, "w") as f:
                json.dump(result, f, indent=1)

    print(json.dumps({k: (v.get("reduction_x") if isinstance(v, dict)
                          else v) for k, v in result.items()}), flush=True)


if __name__ == "__main__":
    main()
