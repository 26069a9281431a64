"""Share of the prompt tokens admitted in the window that the prefix cache
served, so they were not prefilled: the difference across the window of
`Engine.stats["prefill_tokens_saved"]` over that of saved plus
`Engine.stats["prefill_tokens"]`. Layer: scheduler and page pool."""


def read(run):
    saved = run.stats.get("prefill_tokens_saved", 0)
    total = saved + run.stats.get("prefill_tokens", 0)
    return 100.0 * saved / total if total > 0 else None
