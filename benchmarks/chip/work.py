"""Work counted from shapes: parameter and cache bytes, model FLOPs.

Everything here is computed from a configuration file under
``benchmarks/chip/configs`` and from request lengths, never from the
program, so the yardstick stays put while the program changes.  Bytes are
those a chip holds or must read; FLOPs count multiply and add as two.

``dims(cfg)`` maps a configuration file's published keys onto the few sizes
the counts need.  Per-chip figures divide by the chips a layer is split
over: the tensor-parallel layout splits every matrix, the embedding, the
output head and the cache four ways, and replicates only the norms.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent

#: Bytes per element of the serving dtypes.
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}

#: Published key names of each ``model_type``.
_KEYS = {
    "chatglm": dict(layers="num_layers", d="hidden_size",
                    ff="ffn_hidden_size", heads="num_attention_heads",
                    kv_heads="multi_query_group_num", head_dim="kv_channels",
                    vocab="padded_vocab_size", eps="layernorm_epsilon"),
    "granite": dict(layers="num_hidden_layers", d="hidden_size",
                    ff="intermediate_size", heads="num_attention_heads",
                    kv_heads="num_key_value_heads", head_dim=None,
                    vocab="vocab_size", eps="rms_norm_eps"),
}


@dataclass(frozen=True)
class Dims:
    """The sizes of a dense, gated-MLP, GQA decoder that the counts need."""

    layers: int
    d: int
    ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int            # real vocabulary (logits that are read)
    vocab_padded: int     # rows held by the embedding and the output head
    eps: float
    rope_variant: str     # "half" (ChatGLM) or "full"
    rope_theta: float
    dtype: str
    chips: int

    @property
    def elem_bytes(self) -> int:
        return DTYPE_BYTES[self.dtype]


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def dims(cfg: dict) -> Dims:
    keys = _KEYS[cfg["model_type"]]
    d = int(cfg[keys["d"]])
    heads = int(cfg[keys["heads"]])
    head_dim = (int(cfg[keys["head_dim"]]) if keys["head_dim"]
                else d // heads)
    vocab = int(cfg[keys["vocab"]])
    pad = int(cfg.get("vocab_pad_to", 1))
    return Dims(layers=int(cfg[keys["layers"]]), d=d, ff=int(cfg[keys["ff"]]),
                heads=heads, kv_heads=int(cfg[keys["kv_heads"]]),
                head_dim=head_dim, vocab=vocab,
                vocab_padded=-(-vocab // pad) * pad,
                eps=float(cfg[keys["eps"]]),
                rope_variant=cfg["rope_variant"],
                rope_theta=float(cfg["rope_theta"]),
                dtype=cfg.get("served_dtype", cfg["torch_dtype"]),
                chips=int(cfg["chips"]))


# --------------------------------------------------------------------------- #
# Parameters and cache
# --------------------------------------------------------------------------- #
def layer_params(m: Dims) -> int:
    """Matrix and norm elements of one decoder layer."""
    attn = m.d * m.head_dim * (m.heads + 2 * m.kv_heads) \
        + m.heads * m.head_dim * m.d
    mlp = 3 * m.d * m.ff
    return attn + mlp + 2 * m.d


def param_count(m: Dims) -> int:
    """Every element the chips hold: layers, embedding, head, final norm."""
    return m.layers * layer_params(m) + 2 * m.vocab_padded * m.d + m.d


def param_bytes(m: Dims) -> int:
    """Bytes of all weights, over all chips."""
    return param_count(m) * m.elem_bytes


def kv_bytes_per_token(m: Dims) -> int:
    """Cache bytes one position takes on one chip (keys and values)."""
    return 2 * m.layers * m.kv_heads * m.head_dim * m.elem_bytes // m.chips


def matmul_params(m: Dims) -> int:
    """Weights one token multiplies through: every layer, the output head
    over the real vocabulary.  The embedding is a lookup, not a product."""
    return m.layers * (layer_params(m) - 2 * m.d) + m.d * m.vocab


# --------------------------------------------------------------------------- #
# FLOPs
# --------------------------------------------------------------------------- #
def attn_flops(m: Dims, kv_len: int) -> int:
    """Scores and weighted values of one query over ``kv_len`` positions."""
    return 4 * m.layers * m.heads * m.head_dim * kv_len


def prefill_flops(m: Dims, prompt_len: int) -> int:
    """Model FLOPs of one prompt: 2 per weight per token, causal attention."""
    causal = prompt_len * (prompt_len + 1) // 2
    return 2 * matmul_params(m) * prompt_len \
        + 4 * m.layers * m.heads * m.head_dim * causal


def decode_flops(m: Dims, kv_len: int) -> int:
    """Model FLOPs of one generated token attending ``kv_len`` positions."""
    return 2 * matmul_params(m) + attn_flops(m, kv_len)


def request_flops(m: Dims, prompt_len: int, gen_len: int) -> int:
    """All model FLOPs of one request: its prompt, then ``gen_len - 1``
    decode steps (the first token comes from the prompt's pass), step ``k``
    attending ``prompt_len + k`` positions."""
    steps = gen_len - 1
    kv_sum = steps * prompt_len + steps * (steps + 1) // 2
    return prefill_flops(m, prompt_len) + 2 * matmul_params(m) * steps \
        + 4 * m.layers * m.heads * m.head_dim * kv_sum


def decode_live_positions(prompt_lens, gen_lens) -> int:
    """Cache positions read over all decode steps of a job, summed."""
    total = 0
    for p, g in zip(prompt_lens, gen_lens):
        steps = g - 1
        total += steps * p + steps * (steps + 1) // 2
    return total


def decode_step_bytes(m: Dims, rows: int, live_positions: float) -> float:
    """Least bytes one chip reads in a decode step: every weight except the
    embedding table (``rows`` of it are looked up), plus the live cache."""
    weights = (param_count(m) - m.vocab_padded * m.d) * m.elem_bytes
    lookup = rows * m.d * m.elem_bytes
    return (weights + lookup) / m.chips \
        + kv_bytes_per_token(m) * live_positions


def prefill_step_bytes(m: Dims, rows: int, prompt_len: int) -> float:
    """Least bytes one chip moves in a prefill of ``rows`` prompts: every
    weight read once, the rows' cache written."""
    weights = (param_count(m) - m.vocab_padded * m.d) * m.elem_bytes
    return weights / m.chips + kv_bytes_per_token(m) * rows * prompt_len


def roofline_s(flops: float, nbytes: float, peak: dict,
               chips: int = 1) -> tuple[float, str]:
    """Least seconds one chip needs for its share: the larger of FLOPs over
    peak FLOP/s and bytes over peak bandwidth, and which one bounds it.
    ``flops`` is the whole step's; ``nbytes`` is already per chip."""
    t_f = flops / chips / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")


def load_peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table['devices'])})"
                       ) from None
