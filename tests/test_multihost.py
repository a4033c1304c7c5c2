"""Multi-host runtime (distributed/): bootstrap config validation,
host-topology derivation, rendezvous records, the fleet clock handshake,
cross-process-count residual resharding, the lossless (ZipCCL-style)
comm mode, and the per-host trace merge.

Everything here runs single-process on the suite's 8 simulated CPU
devices except the final slow test, which spawns a real 2-process
localhost fleet (gloo collectives) and asserts its per-step losses are
BIT-IDENTICAL to an equivalent single-process mesh — the property
scripts/multihost_drill.py's max_loss_delta == 0.0 acceptance rides on.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from deeperspeed_tpu.distributed import topology as dtopo
from deeperspeed_tpu.distributed.config import DistributedConfig
from deeperspeed_tpu.runtime.comm.config import CommConfig
from deeperspeed_tpu.runtime.comm.reducer import GradReducer
from deeperspeed_tpu.runtime.config import ConfigError, TrainingConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------- #
# DistributedConfig validation
# --------------------------------------------------------------------- #

def test_distributed_config_defaults():
    cfg = DistributedConfig()
    assert cfg.enabled and cfg.coordinator_address is None
    assert cfg.num_processes is None and cfg.process_id is None
    assert cfg.cpu_collectives == "auto"


def test_distributed_config_rejects_unknown_key():
    with pytest.raises(ValueError, match="unknown"):
        DistributedConfig.from_dict({"enabled": True, "cordinator": "x:1"})


def test_distributed_config_rejects_bare_host():
    # a coordinator address without a port can only rendezvous by luck
    with pytest.raises(ValueError, match="host:port"):
        DistributedConfig(coordinator_address="10.0.0.1")


def test_distributed_config_pins_shape_together():
    with pytest.raises(ValueError, match="process_id"):
        DistributedConfig(num_processes=2)
    with pytest.raises(ValueError, match="process_id"):
        DistributedConfig(process_id=0)
    cfg = DistributedConfig(coordinator_address="127.0.0.1:9999",
                            num_processes=2, process_id=1)
    assert (cfg.num_processes, cfg.process_id) == (2, 1)


def test_distributed_config_rejects_bad_collectives():
    with pytest.raises(ValueError, match="cpu_collectives"):
        DistributedConfig(cpu_collectives="nccl")


def test_training_config_distributed_block():
    cfg = TrainingConfig({"train_batch_size": 8,
                          "distributed": {"cpu_collectives": "gloo"}},
                         world_size=1)
    assert cfg.distributed_enabled
    assert cfg.distributed_config().cpu_collectives == "gloo"
    # explicit off: block present but inert
    cfg = TrainingConfig({"train_batch_size": 8,
                          "distributed": {"enabled": False}}, world_size=1)
    assert not cfg.distributed_enabled
    assert cfg.distributed_config() is None
    # a typo'd knob fails at config time, not at bootstrap
    with pytest.raises(ConfigError, match="distributed"):
        TrainingConfig({"train_batch_size": 8,
                        "distributed": {"cordinator_address": "x:1"}},
                       world_size=1)


# --------------------------------------------------------------------- #
# topology: per-host roles + intra-size derivation
# --------------------------------------------------------------------- #

def test_host_role_suffix():
    from deeperspeed_tpu.monitor.runctx import host_role

    assert host_role("trainer", 0, 1) == "trainer"
    assert host_role("trainer", 2, 4) == "trainer.h2"


class _FakeDev:
    def __init__(self, process_index):
        self.process_index = process_index


def _fake_mesh(proc_of_rank):
    """A mesh stand-in whose ranks map to the given process indices."""
    class M:
        axis_names = ("data",)
        devices = np.array([_FakeDev(p) for p in proc_of_rank],
                           dtype=object)
    return M()


def test_derive_intra_size_contiguous_blocks():
    # 2 hosts x 4 devices, contiguous: the in-host group size is 4
    mesh = _fake_mesh([0, 0, 0, 0, 1, 1, 1, 1])
    assert dtopo.derive_intra_size(mesh, ("data",)) == 4


def test_derive_intra_size_rejects_straddling_layout():
    # interleaved placement: any contiguous block straddles hosts, so
    # the hierarchical schedule must fall back to flat
    mesh = _fake_mesh([0, 1, 0, 1])
    assert dtopo.derive_intra_size(mesh, ("data",)) is None
    # unequal runs (3+1) likewise
    mesh = _fake_mesh([0, 0, 0, 1])
    assert dtopo.derive_intra_size(mesh, ("data",)) is None


def test_derive_intra_size_single_process_is_none():
    mesh = _fake_mesh([0, 0, 0, 0])
    assert dtopo.derive_intra_size(mesh, ("data",)) is None


def test_intra_inter_split_groups():
    intra, inter = dtopo.intra_inter_split(8, 4)
    assert intra == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert inter == [[0, 4], [1, 5], [2, 6], [3, 7]]
    with pytest.raises(ValueError, match="divide"):
        dtopo.intra_inter_split(8, 3)


def test_process_groups_single_process():
    groups = dtopo.process_groups()
    assert list(groups) == [0]
    assert groups[0] == list(range(len(jax.devices())))
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    assert not dtopo.is_process_spanning(mesh)
    desc = dtopo.describe(mesh)
    assert desc["devices"] == 8 and not desc["process_spanning"]


# --------------------------------------------------------------------- #
# rendezvous records + clock handshake
# --------------------------------------------------------------------- #

def test_host_record_round_trip(tmp_path):
    from deeperspeed_tpu.distributed import rendezvous as rdzv

    rec = rdzv.HostRecord(host=1, pid=4242, incarnation=2, epoch=3,
                          role="trainer.h1", status="ready",
                          clock={"wall": 12.0, "perf": 1.0})
    rdzv.write_record(str(tmp_path), rec)
    back = rdzv.read_record(str(tmp_path), 1)
    assert back.host == 1 and back.status == "ready"
    assert back.role == "trainer.h1" and back.epoch == 3
    assert back.clock == {"wall": 12.0, "perf": 1.0}
    assert back.wall > 0  # stamped at write time
    # unknown status is a construction error, not a torn file
    with pytest.raises(ValueError, match="status"):
        rdzv.HostRecord(host=0, status="zombie")


def test_read_records_sorted_and_tolerant(tmp_path):
    from deeperspeed_tpu.distributed import rendezvous as rdzv

    for h in (2, 0, 1):
        rdzv.write_record(str(tmp_path), rdzv.HostRecord(host=h))
    (tmp_path / "host9.json").write_text("{torn")     # ignored
    (tmp_path / "notes.txt").write_text("hi")         # ignored
    recs = rdzv.read_records(str(tmp_path))
    assert [r.host for r in recs] == [0, 1, 2]


def test_wait_all_ready_barrier(tmp_path):
    from deeperspeed_tpu.distributed import rendezvous as rdzv

    for h in range(2):
        rdzv.write_record(str(tmp_path), rdzv.HostRecord(
            host=h, epoch=5, status="ready"))
    recs = rdzv.wait_all_ready(str(tmp_path), hosts=2, epoch=5,
                               timeout_s=5.0)
    assert [r.host for r in recs] == [0, 1]
    # a straggler (stale epoch) times out with its status named
    rdzv.write_record(str(tmp_path), rdzv.HostRecord(
        host=1, epoch=4, status="launched"))
    with pytest.raises(TimeoutError, match="launched"):
        rdzv.wait_all_ready(str(tmp_path), hosts=2, epoch=5,
                            timeout_s=0.2, poll_s=0.02)


def test_offsets_round_trip(tmp_path):
    from deeperspeed_tpu.distributed import rendezvous as rdzv

    rdzv.write_offsets(str(tmp_path), {"trainer.h0": 0.0,
                                       "trainer.h1": 0.25})
    assert rdzv.read_offsets(str(tmp_path)) == {"trainer.h0": 0.0,
                                                "trainer.h1": 0.25}
    assert rdzv.read_offsets(str(tmp_path / "missing")) == {}


def test_clock_offset_estimate():
    from deeperspeed_tpu.monitor.runctx import estimate_clock_offset

    # child clock 10s ahead, 1s round trip: offset recovers the skew
    assert estimate_clock_offset(100.0, 110.5, 101.0) == pytest.approx(
        10.0, abs=1e-9)


# --------------------------------------------------------------------- #
# fleet supervisor pieces (pure logic; the subprocess paths are the
# drill's job)
# --------------------------------------------------------------------- #

def test_classify_exit():
    from deeperspeed_tpu.distributed.fleet import classify_exit

    assert classify_exit(0, 86) == "done"
    assert classify_exit(86, 86) == "preempted"
    assert classify_exit(1, 86) == "crashed"
    assert classify_exit(-9, 86) == "crashed"   # SIGKILL


def test_fleet_policy_defaults(tmp_path):
    from deeperspeed_tpu.distributed.fleet import FleetPolicy, free_port

    pol = FleetPolicy(rendezvous_dir=str(tmp_path))
    assert pol.procs == 2 and pol.base_role == "trainer"
    assert pol.coordinator_host == "127.0.0.1"
    port = free_port()
    assert 0 < port < 65536


def test_cross_host_growth_predicate():
    from deeperspeed_tpu.lifecycle.remesh import cross_host_growth_needed

    assert cross_host_growth_needed(9, 8)        # pool > device cap
    assert not cross_host_growth_needed(8, 8)
    assert not cross_host_growth_needed(2, 8)
    assert not cross_host_growth_needed(None, 8)


# --------------------------------------------------------------------- #
# residual reshard across PROCESS counts (2x2 -> 3x2 fleet growth)
# --------------------------------------------------------------------- #

def _plan(world, lengths, padded, mode="int8", ef=True):
    return {"mode": mode, "world": world, "block": 256, "hier_k": None,
            "canonical": 0, "error_feedback": ef,
            "bucket_lengths": list(lengths), "bucket_padded": list(padded)}


def test_reshard_residuals_across_process_counts():
    """The fleet's 2->3 process growth (2 local devices each) is a
    4->6 world-size change; saved error-feedback residuals must carry
    over sum-preservingly, exactly like the single-host elastic path."""
    from deeperspeed_tpu.resilience import (plans_reshardable,
                                            reshard_comm_residuals)

    saved, target = _plan(4, [100], [120]), _plan(6, [100], [120])
    assert plans_reshardable(saved, target) is None  # None = compatible
    rng = np.random.default_rng(0)
    e = rng.normal(size=(4, 120)).astype(np.float32)
    e[:, 100:] = 0.0
    out = reshard_comm_residuals([{"e": e}], saved, target)
    got = out[0]["e"]
    assert got.shape == (6, 120)
    np.testing.assert_allclose(got[:, :100].sum(axis=0),
                               e[:, :100].sum(axis=0), rtol=1e-6)


# --------------------------------------------------------------------- #
# lossless (ZipCCL-style) comm mode
# --------------------------------------------------------------------- #

def _mesh():
    return Mesh(np.array(jax.devices()[:8]), ("data",))


def _stacked_tree(seed=0, world=8):
    rng = np.random.default_rng(seed)
    return {
        "w1": jnp.asarray(rng.normal(size=(world, 40, 5))
                          .astype(np.float32)),
        "b1": jnp.asarray(rng.normal(size=(world, 13)).astype(np.float32)),
    }


def _reduce(mode, stacked, **kw):
    red = GradReducer(CommConfig(mode=mode, bucket_mb=0.0005, **kw),
                      _mesh())
    red.build_plan(jax.tree.map(lambda x: x[0], stacked))
    out, state = red.reduce_dispatch(stacked, red.init_state())
    return red, out, state


def test_lossless_flat_bit_identical_to_pairwise_tree():
    """Byte-plane transport is a bijection: the lossless mode's result
    must be BITWISE equal to the fixed pairwise reduction tree computed
    locally (no wire error at all), and it must carry no residual
    state. This order-independence is what makes a 2-process fleet's
    losses bit-identical to the single-process mesh."""
    from deeperspeed_tpu.runtime.comm.reducer import pairwise_slot_sum

    stacked = _stacked_tree()
    red_l, out_l, state_l = _reduce("lossless", stacked)
    assert all(not d for d in red_l.init_state())
    assert not jax.tree.leaves(state_l)
    for k in stacked:
        a = np.asarray(out_l[k])
        want = np.asarray(pairwise_slot_sum(stacked[k]) / 8.0)
        assert a.tobytes() == want.tobytes(), k
        np.testing.assert_allclose(
            a, np.asarray(stacked[k]).mean(axis=0), atol=1e-6)


def test_lossless_hierarchical_matches_mean():
    stacked = _stacked_tree(seed=3)
    red, out, _ = _reduce("lossless", stacked, hierarchical="on",
                          intra_size=4)
    assert red.hier_k == 4
    assert all(not d for d in red.init_state())
    for k in stacked:
        want = np.asarray(stacked[k]).mean(axis=0)
        np.testing.assert_allclose(np.asarray(out[k]), want,
                                   atol=1e-6 * max(1.0,
                                                   np.abs(want).max()))


def test_lossless_byte_planes_round_trip():
    x = jnp.asarray(np.random.default_rng(1)
                    .normal(size=(33,)).astype(np.float32))
    planes = GradReducer._to_byte_planes(x)
    assert planes.shape == (4, 33)
    back = GradReducer._from_byte_planes(planes)
    assert np.asarray(back).tobytes() == np.asarray(x).tobytes()


def test_lossless_wire_pricing():
    from deeperspeed_tpu.runtime.comm.wiremodel import (hier_wire_split,
                                                        mode_wire_bits)

    # flat lossless gathers W fp32 replicas: 32*W bits/elem at W=8
    assert mode_wire_bits("lossless", world=8) == 128.0
    assert mode_wire_bits("lossless", world=2) == 32.0
    red, _, _ = _reduce("lossless", _stacked_tree(), hierarchical="on",
                        intra_size=4)
    split = hier_wire_split(red.plan, red.cfg, world=8, intra_size=4)
    assert split["intra_bytes"] > 0 and split["inter_bytes"] > 0
    assert split["total_bytes"] == pytest.approx(
        split["intra_bytes"] + split["inter_bytes"])
    # the cross-host hop moves FAR fewer bytes than flat all-gather
    # (that asymmetry is the whole point of the two-level schedule)
    assert split["inter_bytes"] < split["intra_bytes"]


def test_autotune_space_includes_lossless():
    from deeperspeed_tpu.autotune.space import enumerate_comm_variants

    modes = {c.block["mode"] for c in enumerate_comm_variants()
             if c.block}
    assert "lossless" in modes and "int8" in modes


# --------------------------------------------------------------------- #
# dist/ trace schema + per-host merge
# --------------------------------------------------------------------- #

def _ev(name, args, ts=1.0):
    return {"name": name, "ph": "i", "pid": 1, "tid": 1, "ts": ts,
            "args": args}


def test_validator_accepts_dist_events():
    # only dist/init is a trace event; fleet-side coordination
    # (rendezvous, barriers, growth) lives in the restart JSONL and the
    # rendezvous records, never in a trace lane
    from deeperspeed_tpu.monitor.validate import validate_events

    events = [
        _ev("dist/init", {"process": 0, "processes": 2,
                          "local_devices": 2, "global_devices": 4}),
    ]
    assert validate_events(events, strict=True) == []


def test_validator_rejects_torn_dist_args():
    from deeperspeed_tpu.monitor.validate import validate_events

    probs = validate_events([_ev("dist/init", {"process": 0})],
                            strict=True)
    assert probs and "missing" in probs[0]


def _host_trace(dirpath, role, wall, names):
    doc = {"traceEvents": [
        {"name": n, "ph": "i", "pid": 1, "tid": 1, "ts": 1000.0 * i}
        for i, n in enumerate(names)],
        "otherData": {"run": {"run_id": "r1", "role": role,
                              "incarnation": 0},
                      "clock": {"wall": wall, "perf": 0.0}}}
    path = os.path.join(dirpath, f"{role}.i0.trace.json")
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def test_aggregate_merges_obs_directory_with_offsets(tmp_path):
    """A fleet obs directory (per-host traces + the supervisor's
    offsets.json sidecar) merges into one timeline with each host's
    clock skew taken back out."""
    from deeperspeed_tpu.monitor.aggregate import (expand_sources,
                                                   merge_files)

    obs = tmp_path / "obs"
    obs.mkdir()
    _host_trace(str(obs), "trainer.h0", wall=100.0, names=["run/a"])
    _host_trace(str(obs), "trainer.h1", wall=100.0, names=["run/b"])
    # h1's clock runs 0.5s ahead; the handshake ledger says so
    (obs / "offsets.json").write_text(
        json.dumps({"trainer.h1": 0.5}))

    files = expand_sources([str(obs)])
    assert len(files) == 2 and all(f.endswith(".trace.json")
                                   for f in files)

    doc, stats = merge_files([str(obs)])
    assert stats["unaligned_sources"] == 0
    ts = {e["name"]: e["ts"] for e in doc["traceEvents"]
          if e.get("ph") == "i"}
    # identical anchors + identical raw ts would collide; the offset
    # pulls h1 back by exactly 0.5s
    assert ts["run/a"] - ts["run/b"] == pytest.approx(0.5e6, rel=1e-6)


# --------------------------------------------------------------------- #
# the real thing: a 2-process localhost fleet, bit-identical to a
# single-process mesh
# --------------------------------------------------------------------- #

_PARITY_WORKER = """\
import json, os, sys
rank, world, port, outdir, localdev = (
    int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4],
    int(sys.argv[5]))
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)
from deeperspeed_tpu.distributed.config import DistributedConfig
from deeperspeed_tpu.distributed import bootstrap as bs

if world > 1:
    cfg = DistributedConfig(
        coordinator_address=f"127.0.0.1:{port}", num_processes=world,
        process_id=rank, local_devices=localdev,
        rendezvous_dir=os.path.join(outdir, "rdzv"))
else:
    cfg = DistributedConfig(local_devices=localdev)
topo = bs.bootstrap(cfg)

import jax
import jax.numpy as jnp
import numpy as np
import deeperspeed_tpu as ds
from deeperspeed_tpu.parallel import build_mesh

assert jax.device_count() == 4, jax.devices()
assert topo.process_count == world, topo

def loss_fn(p, batch):
    x, y = batch
    h = jnp.tanh(x @ p["w1"] + p["b1"])
    return jnp.mean((h @ p["w2"] + p["b2"] - y) ** 2)

k1, k2 = jax.random.split(jax.random.PRNGKey(0))
params = {
    "w1": jax.random.normal(k1, (12, 16), jnp.float32) * 0.2,
    "b1": jnp.zeros((16,), jnp.float32),
    "w2": jax.random.normal(k2, (16, 1), jnp.float32) * 0.2,
    "b2": jnp.zeros((1,), jnp.float32),
}
engine, _, _, _ = ds.initialize(
    model=loss_fn, model_parameters=params,
    config={
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        # lossless transport + canonical slots: grads/losses are
        # combined by a graph-fixed pairwise tree over C=4 slots, never
        # a GSPMD mean, so the reduction order cannot depend on how
        # devices map to processes
        "comm": {"mode": "lossless", "bucket_mb": 0.01,
                 "hierarchical": "off"},
        "elasticity": {"enabled": True, "max_train_batch_size": 8,
                       "micro_batch_sizes": [2], "min_gpus": 1,
                       "max_gpus": 8, "version": 0.1,
                       "canonical_shards": 4},
    }, mesh=build_mesh({"data": 4}))

rng = np.random.default_rng(7)
x = rng.normal(size=(8, 12)).astype(np.float32)
y = (x[:, :1] * 1.5 - 0.5).astype(np.float32)
# multi-host data contract (sharding.place_batch): each process feeds
# its own contiguous slice of the global batch, in process order
rows = 8 // world
xl = x[rank * rows:(rank + 1) * rows]
yl = y[rank * rows:(rank + 1) * rows]
losses = ["%.17e" % float(jax.device_get(engine.train_batch((xl, yl))))
          for _ in range(5)]
if rank == 0:
    with open(os.path.join(outdir, f"losses_w{world}.json"), "w") as f:
        json.dump({"losses": losses, "role": os.environ.get(
            "DS_TPU_ROLE", "")}, f)
print(f"rank{rank}/{world} done", flush=True)
"""


@pytest.mark.slow
def test_two_process_losses_bit_identical(tmp_path):
    from deeperspeed_tpu.distributed.bootstrap import multiprocess_cpu_probe
    from deeperspeed_tpu.distributed.fleet import free_port

    if not multiprocess_cpu_probe():
        pytest.skip("no multiprocess CPU collectives in this jaxlib")
    worker = tmp_path / "worker.py"
    worker.write_text(_PARITY_WORKER)
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               DS_TPU_WORLD_SIZE="4")
    env.pop("XLA_FLAGS", None)

    def run(rank, world, localdev, port):
        return subprocess.Popen(
            [sys.executable, str(worker), str(rank), str(world),
             str(port), str(tmp_path), str(localdev)],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)

    # 2 processes x 2 devices
    port = free_port()
    procs = [run(r, 2, 2, port) for r in range(2)]
    for p in procs:
        out, _ = p.communicate(timeout=240)
        assert p.returncode == 0, out[-3000:]
    # 1 process x 4 devices, same global mesh
    ref = run(0, 1, 4, 0)
    out, _ = ref.communicate(timeout=240)
    assert ref.returncode == 0, out[-3000:]

    multi = json.loads((tmp_path / "losses_w2.json").read_text())
    single = json.loads((tmp_path / "losses_w1.json").read_text())
    assert multi["losses"] == single["losses"], (multi, single)
    assert multi["role"] == "trainer.h0"  # per-host obs lane
    # bootstrap stamped both hosts' ready records
    from deeperspeed_tpu.distributed import rendezvous as rdzv
    recs = rdzv.read_records(str(tmp_path / "rdzv"))
    assert [r.host for r in recs] == [0, 1]
    assert all(r.status == "ready" and r.clock for r in recs)
