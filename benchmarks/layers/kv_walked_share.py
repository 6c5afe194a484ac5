"""Kernels (`ops/paged_attention.py`): the share of the live contexts'
pages that the paged decode kernel is given to walk, over the window:
`stats()['paged_kernel']` `walked_pages` (summed over the layers; a
window layer's being the pages that hold its last `sliding_window`
keys) over `live_pages` x the configuration's layers, close minus
open.  100% where every layer walks every page; with three window
layers of 4096 and one full layer at contexts of 8.4k, 62%.  From the
host's own depth of each slot; what the kernel then does with the
pages shows in `paged_attn_roofline`.  A program without the counter
reports nothing."""


def compute(run):
    k0 = run.stats0.get('paged_kernel') or {}
    k1 = run.stats1.get('paged_kernel') or {}
    if 'walked_pages' not in k0 or 'walked_pages' not in k1:
        return None
    live = k1['live_pages'] - k0['live_pages']
    if live <= 0:
        return None
    return (100.0 * (k1['walked_pages'] - k0['walked_pages']) /
            (live * run.model['num_hidden_layers']))
