"""Work and peak arithmetic, pinned at the configurations' published
widths, reached through each configuration's model description."""

import pytest

import chip_paths  # noqa: F401  (before the modules below)
import traffic
import work


def _described(name):
    cfg = work.load_config(name)
    model = work.description(cfg)
    return model, model.dims(cfg)


@pytest.fixture(scope="module")
def dense():
    return work.description(work.load_config("chatglm3-6b"))


@pytest.fixture(scope="module")
def glm():
    return _described("chatglm3-6b")[1]


@pytest.fixture(scope="module")
def granite():
    return _described("granite-3-8b-tp4")[1]


def test_chatglm3_parameter_and_cache_bytes(dense, glm):
    assert glm.vocab_padded == 65024     # already a multiple of 256
    assert dense.param_bytes(glm) == 12_486_909_952
    assert round(dense.param_bytes(glm) / 1e9, 2) == 12.49
    assert dense.kv_bytes_per_token(glm) == 28 * 2 * 2 * 128 * 2 == 28_672


def test_granite_parameter_and_per_chip_cache_bytes(dense, granite):
    assert granite.chips == 4 and granite.vocab_padded == 49408
    assert round(dense.param_bytes(granite) / 1e9, 2) == 16.75
    assert dense.kv_bytes_per_token(granite) == 40 * 2 * 8 * 128 * 2 // 4 \
        == 40_960


@pytest.mark.parametrize("name,params,kv,decode,prefill,chat_flops", [
    # Computed by the dense counts of work.py before they moved into
    # models/dense_gqa.py; the move may not change one of them.
    ("chatglm3-6b", 6_243_454_976, 28_672, 11_960_233_984.0,
     12_071_673_856.0, 296_565_938_388_992),
    ("granite-3-8b-tp4", 8_374_259_712, 40_960, 4_094_437_376.0,
     4_253_714_432.0, 405_554_495_528_960),
])
def test_counts_are_those_before_the_move(name, params, kv, decode, prefill,
                                          chat_flops):
    model, m = _described(name)
    assert model.param_count(m) == params
    assert model.kv_bytes_per_token(m) == kv
    assert model.decode_step_bytes(m, 8, 207.0) == decode
    assert model.prefill_step_bytes(m, 8, 512) == prefill
    # Summed over chat-decode's 88 requests (1.72/s over 51 s).
    prompts, gens = traffic.lengths(traffic.load_mix("chat-decode"), 88)
    assert sum(model.request_flops(m, int(p), int(g))
               for p, g in zip(prompts, gens)) == chat_flops


def test_request_flops_is_prefill_plus_each_decode_step(dense, glm):
    p, g = 100, 7
    steps = sum(dense.decode_flops(glm, p + k) for k in range(1, g))
    assert dense.request_flops(glm, p, g) == \
        dense.prefill_flops(glm, p) + steps
    # 2 FLOPs per weight per token: 5.98e9 weights multiply each token.
    assert 2 * dense.matmul_params(glm) == 11_953_766_400


def test_decode_live_positions_counts_each_step():
    # prompt 10: steps 1..3 read 11, 12, 13 positions.
    assert work.decode_live_positions([10], [4]) == 36
    assert work.decode_live_positions([10, 5], [4, 1]) == 36


def test_decode_step_bytes_skip_the_embedding_table(dense, glm, granite):
    weights = dense.param_bytes(glm) - glm.vocab_padded * glm.d * 2
    assert dense.decode_step_bytes(glm, 8, 0) == weights + 8 * glm.d * 2
    assert dense.decode_step_bytes(granite, 16, 100) == pytest.approx(
        (dense.param_bytes(granite) - granite.vocab_padded * granite.d * 2
         + 16 * granite.d * 2) / 4 + 40_960 * 100)


def test_roofline_names_its_bound():
    peak = work.load_peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    t, bound = work.roofline_s(197e12, 819e9 / 2, peak)
    assert (t, bound) == (1.0, "flops")
    t, bound = work.roofline_s(4 * 197e12, 2 * 819e9, peak, chips=4)
    assert (t, bound) == (2.0, "bytes")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.load_peaks("TPU v9 imaginary")


@pytest.mark.parametrize("description,message", [
    (None, "names no model description"),
    ("../run", "names no model description"),
    ("no_such_model", "there is no benchmarks/chip/models/no_such_model.py"),
])
def test_a_config_without_a_known_description_is_an_error(description,
                                                          message):
    cfg = dict(work.load_config("chatglm3-6b"), name="toy")
    cfg.pop("description")
    if description is not None:
        cfg["description"] = description
    with pytest.raises(ValueError, match=message) as err:
        work.description(cfg)
    assert "benchmarks/chip/configs/toy.json" in str(err.value)
