"""One BERT bench variant per process (in-process sweeps are unreliable:
HBM is not reliably released between engines).

Usage: python scripts/bert_variant_probe.py SEQ MICRO KEY=VAL...
Keys: remat(0/1) policy gather ce masterless(0/1) stage steps
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bert_sparse_bench import bench_bert  # noqa: E402


def main():
    seq, micro = int(sys.argv[1]), int(sys.argv[2])
    kw = dict(steps=8, warmup=2)
    for arg in sys.argv[3:]:
        k, v = arg.split("=")
        kw[{"remat": "remat", "policy": "remat_policy", "gather": "gather",
            "ce": "ce_chunk", "masterless": "masterless", "stage":
            "zero_stage", "steps": "steps"}[k]] = (
            float(v) if k == "gather" else
            v if k == "policy" else int(v))
    if "remat" in kw:
        kw["remat"] = bool(kw["remat"])
    if "masterless" in kw:
        kw["masterless"] = bool(kw["masterless"])
    r = bench_bert(seq, micro, **kw)
    print("VARIANT", json.dumps(r))


if __name__ == "__main__":
    main()
