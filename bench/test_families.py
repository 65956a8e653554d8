"""The family seam (``bench.families``), on the CPU.

- Every configuration file resolves to a family module whose shapes give
  the program's parameter count and whose leaves lie in the order of the
  program's flat parameter vector.
- Moving the CNN into its module changed no byte: for a fixed seed, dir0.1's
  world is the world of the tree before the move, put in that tree's
  client order (it permuted the clients by the seed's first draw; now each
  client holds the split's share of its own index), and the initial
  weights are that tree's (digests recorded there). The program's record
  of its first 50 updates and the reference's replay of them are pinned on
  the split's order.
- A token family, the program's ``fed-lm-smoke`` through the dense module,
  runs a whole run but the look for a chip: correct when sound, and not
  correct with the control or a fault, under the limits the benchmark's
  cell holds.
"""
from __future__ import annotations

import hashlib
import os

import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct, families, reference, run, traffic
from bench.test_bench import TINY
from bench.test_correct import SEED, SMALL

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = run.load_benchmark()
CONFIGS = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "configs"))
                 if f.endswith(".json"))
LIMITS = traffic.load_json("limits", "cifar10-fedpsa-dir0.1")["limits"]
LM_CELL = {"name": "fed-lm-smoke.tokens-smoke", "config": "fed-lm-smoke",
           "traffic": "tokens-smoke", "chips": 1}
LM_SEED = 2**33 + 17

# sha256 of each part, as ``_digest`` reads it, on the tree before the move
# (worlds as ``_parent_order`` gives them)
PARENT = {
    "world_cifar10_5000":
        "ff54702fc488aa3a703fe6591adcfeb3df89fb2aaeb99deb7421020eee38a167",
    "w0_cifar10":
        "7fff3d6197ff165afa5602e467fe5d40ceb8b9c285b91fac0263e06d16259f27",
    "tiny_world":
        "eb18e0230bd70d7ab45cccbeb83a0ae78bc9d46d2a4d9b46eca197dba27d4c55",
    "tiny_w0":
        "50b36a2e037dbcf09492639260203f1eb3397da95244e69ca05066e13be8b9ac",
}
# the small run's record and replay, clients in the split's order
SPLIT_ORDER = {
    "tiny_record":
        "5333dd283cca4d4f0e9452a8cb4c4a60014b3c46eac9321d78cbe19879f79b2c",
    "tiny_replay":
        "93565313ddec8e3c710c40c4ac5ab8a07d2e657650f7b5a2058b96e2bfa47b3b",
}


def _digest(obj) -> str:
    h = hashlib.sha256()

    def feed(o):
        if isinstance(o, dict):
            for k in sorted(o):
                h.update(str(k).encode())
                feed(o[k])
        elif isinstance(o, (list, tuple)):
            h.update(b"[%d" % len(o))
            for x in o:
                feed(x)
        elif o is None:
            h.update(b"None")
        else:
            a = np.ascontiguousarray(np.asarray(o))
            h.update(str(a.dtype).encode() + str(a.shape).encode())
            h.update(a.tobytes())

    feed(obj)
    return h.hexdigest()


def _parent_order(w, seed: int) -> dict:
    """The world as the tree before the move held it: client i was the one
    that holds share ``perm[i]`` now, ``perm`` the data stream's first
    draw. Samples are drawn class by class in row order, so each class's
    samples, in row order, are the same bytes in both orders."""
    perm = np.random.default_rng(traffic.sub_seeds(seed)["data"]) \
        .permutation(len(w.sizes))
    return {"y_train": np.concatenate([w.client(c)[1] for c in perm]),
            "offsets": np.concatenate([[0], np.cumsum(w.sizes[perm])]),
            "x_by_class": [w.x_train[w.y_train == k]
                           for k in range(w.num_classes)],
            "x_test": w.x_test, "y_test": w.y_test, "calib": w.calib}


@pytest.mark.parametrize("name", CONFIGS)
def test_config_resolves_to_its_family(name):
    """The file names its module; the module's shapes give the file's and
    the program's parameter count, and its leaves are the program's flat
    layout, leaf for leaf."""
    from repro.common import tree as tu
    from repro.models.model import count_params

    config = traffic.load_json("configs", name)
    assert config["name"] == name
    fam = families.load(config)
    assert fam.__name__ == f"bench.families.{config['family']}"
    cfg = run.program_config(config)
    assert fam.params(config) == count_params(cfg)[0] == config["params"]
    w0 = fam.init_weights(config, 3)
    spec = tu.FlatSpec(w0)
    assert list(spec.sizes) == reference.leaf_sizes(config)
    assert np.array_equal(np.asarray(spec.flatten(w0)),
                          reference.flatten(config, w0))


def test_cnn_move_keeps_dir01_bytes():
    config = traffic.load_json("configs", "paper-cifar10-cnn")
    mix = dict(traffic.load_json("workloads", "dir0.1"), samples=5000)
    seed = 2**33 + 5
    assert _digest(_parent_order(traffic.make_world(config, mix, seed),
                                 seed)) == PARENT["world_cifar10_5000"]
    assert _digest(reference.init_weights(config, seed)) \
        == PARENT["w0_cifar10"]
    keep: dict = {}
    run.run_cell(run.find_cell(BENCH, "cifar10-fedpsa-dir0.1"), SEED, 0.0,
                 False, bench=BENCH, require_tpu=False, config=dict(TINY),
                 traffic_overrides=SMALL, limits={}, keep=keep)
    assert _digest(_parent_order(keep["world"], SEED)) == PARENT["tiny_world"]
    assert _digest(keep["w0"]) == PARENT["tiny_w0"]
    got = {"tiny_record": _digest(keep["record"].as_outputs()),
           "tiny_replay": _digest(keep["want"])}
    assert got == SPLIT_ORDER


@pytest.fixture(scope="module")
def lm_sound():
    keep: dict = {}
    res = run.run_cell(LM_CELL, LM_SEED, 0.0, False, bench=BENCH,
                       require_tpu=False, limits=LIMITS, keep=keep)
    return res, keep


def test_token_family_run_is_correct(lm_sound):
    res, keep = lm_sound
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert keep["world"].x_train.dtype == np.int32
    assert len(keep["record"].arrivals) == correct.ARRIVALS


def test_token_family_control_is_not_correct(lm_sound):
    _, keep = lm_sound
    ctl = reference.replay(keep["config"], keep["traffic"], keep["world"],
                           keep["w0"], keep["record"].arrivals,
                           keep["shuffle"], dtype=jnp.bfloat16)
    values = correct.numbers(ctl, keep["want"], keep["w0_flat"],
                             reference.leaf_sizes(keep["config"]))
    ok, checks = correct.verdict(values, LIMITS)
    assert not ok, checks


def test_token_family_fault_is_not_correct():
    res = run.run_cell(LM_CELL, LM_SEED, 0.0, False, bench=BENCH,
                       require_tpu=False, limits=LIMITS, fault="half_batch")
    assert res["correct"] is False, res["checks"]


def test_dense_forward_flops_match_xla():
    """The dense module's count against XLA's cost analysis of its own
    reference forward, unrolled, on the CPU. XLA computes every score of
    the causal attention and the logits of the last position too, and
    counts the norms, RoPE, softmax and SiLU besides: under 15 % here."""
    import jax
    from bench.families import dense

    config = traffic.load_json("configs", "fed-lm-smoke")
    S = 16
    w0 = dense.init_weights(config, 0)
    tok = jnp.zeros((1, S), jnp.int32)
    cost = jax.jit(lambda p, t: dense.forward(p, t, config)).lower(
        w0, tok).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    H, hd = config["num_heads"], config["head_dim"]
    masked = config["num_layers"] * 2 * 2 * H * hd * S * (S - 1) // 2
    last = 2 * config["d_model"] * config["vocab_size"]
    ours = dense.forward_flops(config, {"seq_len": S}) + masked + last
    assert ours <= cost["flops"] <= 1.15 * ours
