"""Model step: of the KV blocks that paged attention gathered in the
window's decode steps (every table entry of every slot, per attention
application), the share that the live slots' contexts fill, in %: the
program's counters ``serve.attn_blocks_live`` and
``serve.attn_blocks_gathered``, bumped at each decode issue.  Nothing to
read where the program keeps no such counters."""


def read(run):
    from repro import obs
    live = obs.REGISTRY.get("serve.attn_blocks_live")
    gathered = obs.REGISTRY.get("serve.attn_blocks_gathered")
    if live is None or gathered is None or not gathered.value:
        return None
    return 100.0 * live.value / gathered.value
