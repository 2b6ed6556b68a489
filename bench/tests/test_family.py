"""A model family is found by the name its configuration file gives:
a stand-in family, put in place by new modules alone
(``standin/{reference,counts,configs}/toy.py`` on the packages' paths),
is what the harness's weights, reference, service check, count readers
and scope vocabulary take."""

import json
import os
import sys

import jax
import numpy as np
import pytest

from bench import family, scopes
from bench import run as bench_run
from bench import tracereduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
STANDIN = os.path.join(HERE, "standin")
TOY = {"name": "toy_tiny", "family": "toy", "named_config": "tiny",
       "model": {"channels": 3, "nlat": 4, "nlon": 8}, "weights_seed": 3,
       "request": {"precision": "bfloat16"}}


@pytest.fixture
def toy(monkeypatch, tmp_path):
    """The stand-in family on the import paths of ``bench.reference``,
    ``bench.counts`` and ``repro.configs``, and the benchmark's cache in
    a directory of its own."""
    import bench.counts
    import bench.reference
    import repro.configs
    for pkg, sub in ((bench.reference, "reference"), (bench.counts, "counts"),
                     (repro.configs, "configs")):
        monkeypatch.setattr(pkg, "__path__",
                            list(pkg.__path__) + [os.path.join(STANDIN, sub)])
    monkeypatch.setattr(bench_run, "CACHE", str(tmp_path))
    monkeypatch.setattr(bench_run, "free_device", lambda: None)
    yield dict(TOY)
    for name in ("bench.reference.toy", "bench.counts.toy",
                 "repro.configs.toy"):
        sys.modules.pop(name, None)


def test_a_configuration_must_name_its_family():
    with open(os.path.join(HERE, "data", "fcn3_smoke.json")) as f:
        assert family.name(json.load(f)) == "fcn3"
    with pytest.raises(SystemExit, match="names no family"):
        family.reference({"name": "nameless", "model": {}})


def test_weights_are_made_kept_and_restored_by_the_family(toy):
    path = bench_run.weights(toy)
    assert path == os.path.join(bench_run.CACHE, "weights", "toy_tiny",
                                "seed3", "ckpt_00000000")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest == {"step": 0, "keys": ["params/bias", "params/mix/w"],
                        "shardings": {},
                        "extra": {"config": "toy_tiny", "weights_seed": 3}}
    ref, params = bench_run.reference(toy, path)
    assert type(ref).__module__ == "bench.reference.toy"
    want = ref.model.init(jax.random.PRNGKey(3))
    np.testing.assert_array_equal(params["mix"]["w"], want["mix"]["w"])
    np.testing.assert_array_equal(params["bias"], want["bias"])
    # made once: a second call finds the checkpoint and writes nothing
    stamp = os.path.getmtime(os.path.join(path, "arrays.npz"))
    assert bench_run.weights(toy) == path
    assert os.path.getmtime(os.path.join(path, "arrays.npz")) == stamp
    served = bench_run.roll(ref, params, {"sample": 1, "seed": 2,
                                          "members": 2}, 2)
    assert len(served) == 2 and served[0]["spectrum"].shape == (3, 4)


def test_the_service_check_consults_the_familys_registry(toy):
    bench_run.check_config(toy)
    toy["model"] = {**toy["model"], "channels": 5}
    with pytest.raises(SystemExit, match="differs"):
        bench_run.check_config(toy)


def _run(counts) -> dict:
    """A 10 s window: two ``toy_kernel`` calls of 1 s, 4 member-steps,
    the last completion at 8 s."""
    kernel = "%toy_kernel.{} = f32[3,4,8]{{2,1,0}} custom-call(...)"
    trace = tr.Trace(ops={"/device:TPU:0": [(kernel.format(1), 1e9, 2e9),
                                            (kernel.format(2), 5e9, 6e9)]},
                     annotations=[], lo=0.0, hi=10e9)
    return {"member_steps": 4, "busy_to_s": 8.0, "model": TOY["model"],
            "counts": counts, "value_bytes": counts.VALUE_BYTES["bfloat16"],
            "peaks": {"flops_per_s": 1e6, "hbm_bytes_per_s": 1e5},
            "trace": trace}


def test_the_count_readers_take_the_familys_counts(toy):
    from bench.metrics import _kernel, step_mfu
    counts = family.counts(toy)
    assert counts.__name__ == "bench.counts.toy"
    run = _run(counts)
    flops = 3 * 2.0 * 9 * 32                  # 3 calls of a 3x3 mix, 32 points
    assert step_mfu.read(run) == pytest.approx(100 * flops * 4 / 8.0 / 1e6,
                                               rel=1e-12)
    # memory-bound at bfloat16: 2 bytes of (2 * 3 * 32 + 9) values a call
    t_min = 3 * 2 * (2 * 3 * 32 + 9) / 1e5
    assert _kernel.roofline(run, "toy_kernel", "toy_kernel") == \
        pytest.approx(100 * t_min * 4 / 2.0, rel=1e-12)


def test_the_familys_scopes_join_the_vocabulary(toy, monkeypatch):
    monkeypatch.setattr(scopes, "SCOPES", scopes.vocabulary())
    assert set(scopes.SCOPES) == {"toy.block", *family.counts(
        {"family": "fcn3"}).SCOPES, *scopes.ENGINE_SCOPES}
    assert scopes.scope_of("jit(chunk)/while/body/toy.block/dot") == \
        "toy.block"
    assert scopes._scope({"jit(chunk)/vmap(toy.block)/add"}) == "toy.block"
    assert scopes.scope_of("jit(chunk)/engine.noise/add") == "engine.noise"
