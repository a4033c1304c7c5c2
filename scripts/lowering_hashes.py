"""Hashes of the serving programs' lowered text, to show that a change
leaves the programs of the cells the benchmark already has as they were:
the check of any move inside the serving programs (a count of programs and
a hash each; never a time).

    python scripts/lowering_hashes.py [<tree>] [--cells] > a.json

builds, for every serving cell of ``tests/bench/data/BENCHMARK.toy*.json``
in ``<tree>`` (default: this checkout; the toy sizes, for the CPU: the XLA
routes) or, with ``--cells``, of ``<tree>/BENCHMARK.json`` (the cells' own
geometry, for the chip: the kernel routes the cache's choosers pick on a
TPU are compared too; an engine at a time, nothing is compiled or run),
the engine and prints
``{"<cell>:<program>": sha256 of jax.jit(...).lower(...).as_text()}`` for
``ds_decode_step`` and ``ds_prefill_chunk`` (a mixed stack) or
``ds_prefill`` and ``ds_suffix_prefill`` (a stack of attention layers).
Run it on the parent (``git archive <commit> | tar -x -C <dir>``) and on
the change and ``diff`` the two files: the same text lowers to the same
program.
"""

import base64
import gc
import glob
import hashlib
import json
import os
import re
import sys


def without_locations(text: str) -> str:
    """``text`` with the serialized body of every Mosaic kernel in it (a
    ``tpu_custom_call``'s ``body``: base64 of a module that carries the
    file and line of every frame that called the kernel, which move with
    any edit above it) replaced by that module printed without locations.
    A program of XLA routes alone (the toy cells on the CPU) has none."""
    bodies = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text)
    if not bodies:
        return text
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    for body in set(bodies):
        ctx = jmlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(body))
            text = text.replace(
                body, module.operation.get_asm(enable_debug_info=False))
    return text


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    cells = "--cells" in argv
    if cells:
        argv.remove("--cells")
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
        without_locations(lowered.as_text()).encode()).hexdigest()[:16]
    out = {}
    benches = [os.path.join(root, "BENCHMARK.json")] if cells else sorted(
        glob.glob(os.path.join(data, "BENCHMARK.toy*.json")))
    for bench in benches:
        man = mf.Manifest(bench, extra_dirs=[os.path.join(root, "benchmark")])
        devs = jax.devices()[:1]
        for cell in man.cells():
            if man.workload_file(cell)["runner"] == "train":
                continue
            ctx = brun.build_context(man, cell, 7, 1.0, 0, devs,
                                     device.describe(devs), lambda m: None)
            # the last cell's weights and pools go first: a chip holds one
            eng = kv = cache = None
            gc.collect()
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
