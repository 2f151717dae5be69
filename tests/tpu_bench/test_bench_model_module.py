"""A serving cell's model comes from the module its configuration names:
the danube module gives what the harness computed before, a missing or
broken module is an error that names the file, and a second
architecture (``tiny_olmoe``, the program's ``moe`` family) runs through
the harness as a module of its own."""
import copy
import dataclasses
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_olmoe
from conftest import TINY_BENCH, TINY_CHAT, TINY_MODEL, TINY_SERVING
from tpu_bench import counters, danube
from tpu_bench.common import find_cell, model_module
from tpu_bench.run import measure

ARGS = dict(seed=2**34 + 9, seconds=1.5, trace=0)

TINY_MOE = {
    "kind": "serving", "module": "tiny_olmoe", "tenants": 2,
    "model": {"hidden_size": 64, "intermediate_size": 32,
              "num_attention_heads": 4, "num_key_value_heads": 4,
              "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
              "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
              "tie_word_embeddings": False, "num_experts": 8,
              "num_experts_per_tok": 2},
    "program": {"arch": "olmoe-1b-7b",
                "settings": {"num_layers": 2, "d_model": 64, "d_ff": 32,
                             "num_heads": 4, "num_kv_heads": 4,
                             "head_dim": 16, "vocab_size": 256,
                             "vocab_pad_to": 256, "param_dtype": "bfloat16",
                             "num_experts": 8, "experts_per_token": 2,
                             "attn_chunk": 32, "remat": "none"}},
    "engine": TINY_SERVING["engine"], "tenant_spec": {"min_units": 4},
}
#: the tiny chat mix with a limit of its own: in bfloat16 a token near a
#: tie between two experts can switch its route, and the served token's
#: gap then reads up to 0.59 (24 seeds on the CPU), above the dense
#: model's 0.05; the reference on the other tenant's weights reads 4.5
#: or more
MOE_CHAT = dict(TINY_CHAT, check=dict(TINY_CHAT["check"], max_logit_gap=2.0))


def _danube():
    return find_cell("serve-chat")[1]


@pytest.mark.parametrize("S", [256, 512, 1024, 2048])
def test_danube_module_prefill_counts_are_the_counters(S):
    conf = _danube()
    arch, model = model_module(conf), conf["model"]
    assert arch.prefill_flops(model, S) == counters.prefill_flops(model, S)
    assert arch.prefill_bytes(model, S) == counters.prefill_bytes(model, S)


@pytest.mark.parametrize("contexts", [[1], [8], [2176], [1, 8, 2176],
                                      [2176] * 8])
def test_danube_module_decode_counts_are_the_counters(contexts):
    conf = _danube()
    arch, model = model_module(conf), conf["model"]
    assert arch.decode_flops(model, contexts) == \
        counters.decode_flops(model, contexts)
    assert arch.decode_bytes(model, contexts) == \
        counters.decode_bytes(model, contexts)


def test_danube_module_makes_the_same_weights():
    got = model_module(TINY_SERVING).make_weights(TINY_MODEL, 2**40 + 7, 1)
    want = danube.make_weights(TINY_MODEL, 2**40 + 7, 1)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert all(np.array_equal(np.asarray(a).view(np.uint16),
                              np.asarray(b).view(np.uint16))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


@pytest.mark.parametrize("module", [None, "tpu_bench.no_such_model",
                                    "tpu_bench.counters"])
def test_a_serving_config_without_a_sound_module_is_refused(module,
                                                            tmp_path):
    """No module, one that cannot be imported, and one that lacks part
    of the contract: each is an error naming the configuration file,
    raised before the cell builds, never a fall-back to danube."""
    conf = _danube()
    del conf["file"]
    conf.pop("module")
    if module is not None:
        conf["module"] = module
    path = tmp_path / "broken-serving.json"
    path.write_text(json.dumps(conf))
    bench = copy.deepcopy(TINY_BENCH)
    bench["configs"][0]["file"] = str(path)
    with pytest.raises((ValueError, ImportError), match=str(path)):
        measure(SimpleNamespace(workload="serve-chat", **ARGS), bench,
                require_chip=False)


def _program_logits(conf, params, tokens, dtype):
    from repro.models import build_model

    cfg = dataclasses.replace(tiny_olmoe.program_config(conf), dtype=dtype)
    logits, _ = jax.jit(build_model(cfg).prefill_fn)(
        params, {"tokens": tokens[None]})
    return np.asarray(logits[0], np.float32)


def test_moe_reference_is_the_program_in_float32():
    """With the program computing in float32 too, its sorted dispatch
    and the reference's dense experts agree to float32 rounding."""
    model = TINY_MOE["model"]
    params = tiny_olmoe.make_weights(model, 5, 0)
    tokens = jnp.asarray(np.random.default_rng(3).integers(1, 256, 40),
                         jnp.int32)
    ref = np.asarray(tiny_olmoe.reference_logits(
        model, params, tokens, jnp.asarray([39])))[0]
    got = _program_logits(TINY_MOE, params, tokens, "float32")
    assert np.abs(got - ref).max() <= 1e-4 * np.abs(ref).max()


def test_moe_counts_are_of_the_active_experts():
    model = dict(TINY_MOE["model"], num_hidden_layers=1)
    # attention 4·64·64, router 64·8, two of the eight experts 3·64·32
    active = 4 * 64 * 64 + 64 * 8 + 2 * 3 * 64 * 32
    assert tiny_olmoe.decode_flops(model, [3]) == \
        2 * active + 2 * 64 * 256 + 4 * 3 * 4 * 16
    kv_tok = 2 * 4 * 16 * 2
    assert tiny_olmoe.decode_bytes(model, [3]) == \
        (active + 64 * 256) * 2 + 3 * kv_tok + kv_tok


@pytest.mark.parametrize("fault", [None, "reference of the other tenant"])
def test_moe_module_runs_through_the_harness(fault, monkeypatch):
    """Built, timed and checked as the serving cells are; with the
    reference given the other tenant's weights it is not correct."""
    if fault:
        make, calls = tiny_olmoe.make_weights, []

        def swapped(model, seed, tenant):
            # the cell makes each tenant's weights first, then every
            # later call is the reference's
            calls.append(tenant)
            if len(calls) > TINY_MOE["tenants"]:
                tenant = 1 - tenant
            return make(model, seed, tenant)
        monkeypatch.setattr(tiny_olmoe, "make_weights", swapped)
    result, checks = measure(SimpleNamespace(workload="serve-chat", **ARGS),
                             TINY_BENCH, require_chip=False,
                             files=(copy.deepcopy(TINY_MOE),
                                    copy.deepcopy(MOE_CHAT)))
    assert result["correct"] is (fault is None), checks
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
