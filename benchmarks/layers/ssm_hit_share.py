"""Page pool and prefix cache, for a model with mamba layers: the share
of the window's admitted prompt tokens whose state came from the cache
whole, the attention layers' pages and the mamba states of a snapshot
alike (`stats()['ssm']`: `restored_tokens` over `prompt_tokens`, close
minus open).  A mamba state depends on the whole prefix and a page
carries none, so a hit goes only as deep as a snapshot: the tokens
whose pages were cached past it are prefilled again and count as not
restored.  A program without the counter, or a model without mamba
layers, reports nothing."""


def compute(run):
    ssm0, ssm1 = run.stats0.get('ssm'), run.stats1.get('ssm')
    if not ssm0 or not ssm1:
        return None
    prompt = ssm1['prompt_tokens'] - ssm0['prompt_tokens']
    if prompt <= 0:
        return None
    return (100.0 * (ssm1['restored_tokens'] - ssm0['restored_tokens']) /
            prompt)
