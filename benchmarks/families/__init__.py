"""One module an architecture: `families/<family>.py` holds everything
the yardstick knows of one kind of block, computed from a configuration
file's published sizes, and is found by the file's `family` key.

    program_config(model, max_len)      the program's config object
    shapes(model)                       leaf path -> (shape, fan-in or None)
    logits(model, params, tokens, first, rows, precision='float32')
                                        the plain reference; 'int8' its control
    param_counts(model), decode_flops(model, context),
    prefill_flops(model, start, n_new)  what the model needs
    decode_cache_bytes(model, context, kv_dtype),
    decode_attention_flops(model, context)
                                        what one decoded token at that
                                        context reads from the caches and
                                        computes over them

Only `program_config` may import from the program.
"""
import importlib
from typing import Any, Dict


def of(model: Dict[str, Any]):
    return importlib.import_module(f'benchmarks.families.{model["family"]}')
