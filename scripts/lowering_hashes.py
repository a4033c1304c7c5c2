"""Hashes of the serving programs' lowered text at the toy sizes, to show
that a change leaves the programs of the cells the benchmark already has
as they were (a count of programs and a hash each, CPU; never a time).

    python scripts/lowering_hashes.py [<tree>] > a.json

builds, for every serving cell of ``tests/bench/data/BENCHMARK.toy*.json``
in ``<tree>`` (default: this checkout), the toy engine and prints
``{"<cell>:<program>": sha256 of jax.jit(...).lower(...).as_text()}`` for
``ds_decode_step`` and ``ds_prefill_chunk`` (a mixed stack) or
``ds_prefill`` and ``ds_suffix_prefill`` (a stack of attention layers).
Run it on the parent (``git archive <commit> | tar -x -C <dir>``) and on
the change and ``diff`` the two files: the same text lowers to the same
program.
"""

import glob
import hashlib
import json
import os
import sys


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = os.path.abspath(argv[0]) if argv else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    os.chdir(root)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import device, manifest as mf, run as brun
    from benchmark.runners import serve
    from deeperspeed_tpu.models.generation import init_cache
    from deeperspeed_tpu.serving.engine import idle_slots, prefill_chunk_for

    data = os.path.join(root, "tests", "bench", "data")
    sha = lambda lowered: hashlib.sha256(
        lowered.as_text().encode()).hexdigest()[:16]
    out = {}
    for bench in sorted(glob.glob(os.path.join(data, "BENCHMARK.toy*.json"))):
        man = mf.Manifest(bench, extra_dirs=[os.path.join(root, "benchmark")])
        devs = jax.devices()[:1]
        for cell in man.cells():
            if man.workload_file(cell)["runner"] == "train":
                continue
            ctx = brun.build_context(man, cell, 7, 1.0, 0, devs,
                                     device.describe(devs), lambda m: None)
            eng = serve.build_engine(ctx)
            kv, N, bps = eng.kv, eng.scfg.num_slots, eng.scfg.blocks_per_slot
            out[f"{cell}:ds_decode_step"] = sha(eng._decode_step.lower(
                eng.params, kv.k, kv.v, jnp.asarray(idle_slots(N, bps)),
                jnp.zeros(eng._prev.shape, jnp.int32), kv.kc, kv.state))
            if eng._chunk_step is not None:
                C = prefill_chunk_for(eng.cfg, eng.scfg)
                out[f"{cell}:ds_prefill_chunk"] = sha(eng._chunk_step.lower(
                    eng.params, kv.k, kv.v, kv.kc, kv.state,
                    jnp.zeros((1, C), jnp.int32), jnp.zeros((bps,), jnp.int32),
                    np.int32(0), np.int32(0), np.int32(C)))
                continue
            toks = jnp.zeros((1, 32), jnp.int32)
            out[f"{cell}:ds_prefill"] = sha(
                eng._prefill_step.lower(eng.params, toks))
            cache = init_cache(eng.cfg, 1, 64)
            out[f"{cell}:ds_suffix_prefill"] = sha(eng._suffix_prefill.lower(
                eng.params, toks, cache["k"], cache["v"], np.int32(16)))
    print(json.dumps(out, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
