"""Engine tick: the share of the window's prefill chunks that rode a
decode tick, one program reading each layer's weights once for the
chunk's rows and the live slots' tokens (`stats()`
`prefill_chunks_fused` over `prefill_chunks`, close minus open, in
percent).  The rest ran at an engine with no slot live, where there was
no tick to share a read with.  A program without the counter reports
nothing, as does a window in which no chunk ran."""


def compute(run):
    if 'prefill_chunks_fused' not in run.stats1:
        return None
    chunks = run.stats1['prefill_chunks'] - run.stats0['prefill_chunks']
    if chunks <= 0:
        return None
    return 100.0 * (run.stats1['prefill_chunks_fused'] -
                    run.stats0['prefill_chunks_fused']) / chunks
