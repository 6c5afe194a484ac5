"""Family `dense_tied`, known to the tests alone: the dense block with
a tied head (no `lm_head` leaf; logits through the embedding's
transpose).  No configuration of the benchmark uses it.  It is here to
show that a second family reaches `correct` through `run.py` with no
file outside `benchmarks/tests/` knowing its name: the tests put it
where `families.of` looks (`sys.modules`)."""
from benchmarks.families import dense

program_config = dense.program_config     # passes tie_embeddings=True
param_counts = dense.param_counts         # counts no head when tied
decode_flops = dense.decode_flops
prefill_flops = dense.prefill_flops
decode_cache_bytes = dense.decode_cache_bytes
decode_attention_flops = dense.decode_attention_flops


def shapes(model):
    tree = dense.shapes(model)
    del tree[('lm_head', 'kernel')]
    # The table is the head too: logits of order 1, as the dense head's.
    shape, _ = tree[('embed', 'embedding')]
    tree[('embed', 'embedding')] = (shape, model['hidden_size'])
    return tree


def logits(model, params, tokens, first, rows, precision='float32'):
    untied = dict(params, lm_head={
        'kernel': params['embed']['embedding'].T})
    return dense.logits(dict(model, tie_word_embeddings=False), untied,
                        tokens, first, rows, precision)
