"""Share of the chunks a state-space layer's scan walked in the counted
epochs that held no real node (the step records' ``ssm`` block:
``chunks_padding`` over ``chunks``, as the state-space driver sums them):
what the group's padded shape costs the scan.  None where the program
writes no such block."""


def read(facts):
    epochs = facts.get("epochs") or []
    chunks = sum(e.get("ssm_chunks") or 0 for e in epochs)
    padding = sum(e.get("ssm_chunks_padding") or 0 for e in epochs)
    return 100.0 * padding / chunks if chunks else None
