"""Model step: share of the traced device time that went to prefill:
the own time (`reduce.py`, `own_by_name`) of every operation of the
programs `prefill`, `prefill_chunk`, `paged_seed_private`,
`insert_prefill_pages` and `paged_admit_slot`, over chips x the traced
window.  The programs are told by the names the engine jits them under
(`serve/batching_engine.py`, through `models/decode.bind`); an engine
whose tick is not named has none of them to read."""

PREFILL_PROGRAMS = ('prefill/', 'prefill_chunk/', 'paged_seed_private/',
                    'insert_prefill_pages/', 'paged_admit_slot/')
# The tick under its name shows that the engine names its programs (an
# older one jits lambdas and partials): only then is a span without a
# prefill a reading of 0 and not a missing one.
NAMED_TICK = ('paged_engine_step/', 'paged_spec_engine_step/',
              'engine_step/')


def compute(run):
    if run.trace is None or run.trace['window_s'] <= 0:
        return None
    own = run.trace['own_by_name']
    if not any(k.startswith(NAMED_TICK) for k in own):
        return None
    prefill_s = sum(v for k, v in own.items()
                    if k.startswith(PREFILL_PROGRAMS))
    return 100.0 * prefill_s / (run.trace['devices'] *
                                run.trace['window_s'])
