"""Engine tick: tokens the engine generated per tick over the window
(`stats()` `tokens_generated` over `ticks`, close minus open): how full
the batch was when the step ran."""


def compute(run):
    ticks = run.stats1['ticks'] - run.stats0['ticks']
    if ticks <= 0:
        return None
    return (run.stats1['tokens_generated'] -
            run.stats0['tokens_generated']) / ticks
