"""Qwen3 (Hugging Face ``model_type`` ``qwen3``): what the serving driver
and its readers need of the architecture.

Dense decoder layers of grouped-query attention with RMSNorm on each
head's query and key, and a SwiGLU feed-forward; the program serves it
as a model of its ``dense`` family.  Sizes are the configuration file's
Hugging Face keys; the operations and bytes are the dense ones of
``counts.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import counts

# Hugging Face key -> field of the program's ModelConfig
_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
         "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads",
         "intermediate_size": "d_ff", "vocab_size": "vocab",
         "head_dim": "head_dim", "rope_theta": "rope_theta",
         "rms_norm_eps": "norm_eps",
         "tie_word_embeddings": "tie_embeddings"}

# The paged decode kernel has no name of its own in the trace: it is the
# serving path's only Pallas call, so its ops are the Mosaic custom calls.
DECODE_KERNELS = ('custom_call_target="tpu_custom_call"',)

prefill_flops = counts.prefill_flops
decode_flops = counts.decode_flops

# CPU-test widths; ``control`` ones are wide enough that the reference's
# float8 control departs from the served model by more than the limit
_SMALL = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=32,
              vocab_size=512)
_SMALL_CONTROL = dict(_SMALL, hidden_size=256, intermediate_size=512,
                      num_hidden_layers=4, vocab_size=4096)


def program_config(c: Dict[str, Any]):
    """The program's ModelConfig for the configuration file: the repo's
    own entry (``program_config``) with every size taken from the file."""
    from repro.configs import get_config
    over = {v: c[k] for k, v in _KEYS.items()}
    over["dtype"] = c["torch_dtype"]
    return dataclasses.replace(get_config(c["program_config"]), **over)


def init_leaf(path: str, z, c: Dict[str, Any]):
    """The float32 weight at ``path`` (the program's parameter tree, as
    ``jax.tree_util.keystr`` writes it) from its standard normal draw
    ``z``: norm scales 1 + N(0, 0.1^2), the token embedding N(0, 1/d),
    matrices N(0, 1/fan_in).  A tied head is the embedding itself."""
    if "scale" in path:
        return 1.0 + 0.1 * z
    if "tok" in path:
        return z * c["hidden_size"] ** -0.5
    return z * z.shape[-2] ** -0.5


def page_bytes(c: Dict[str, Any], page_size: int) -> float:
    """Bytes the paged decode kernel reads for one page it walks: the
    page's keys and values in every layer."""
    return counts.paged_decode_bytes(c, page_size, 1)


def small(c: Dict[str, Any], control: bool = False) -> Dict[str, Any]:
    """The configuration at CPU-test widths, everything else kept."""
    return dict(c, **(_SMALL_CONTROL if control else _SMALL))
