"""Expert layer (`models/moe.py`): (token, held expert) pairs the
expert layers computed per tick and layer over the window
(`stats()['moe']['held_pairs']` over `ticks` x the configuration's
layers, close minus open): how many tokens' worth of expert products a
tick's read of the held experts' weights carried.  At 64 live slots, 8
experts a token and 16 of 128 experts held the expectation is 64; the
engine's count says how far a run's routing was from it.  A program
without the counter (or a model without experts) reports nothing."""


def compute(run):
    moe0, moe1 = run.stats0.get('moe'), run.stats1.get('moe')
    ticks = run.stats1['ticks'] - run.stats0['ticks']
    if not moe0 or not moe1 or ticks <= 0:
        return None
    return ((moe1['held_pairs'] - moe0['held_pairs']) /
            (ticks * run.model['num_hidden_layers']))
