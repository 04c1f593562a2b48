"""Share of the chunks the gated delta rule walked in the counted epochs
that held no real node (the step records' ``gdn`` block: ``chunks_padding``
over ``chunks``, as the DeltaNet driver sums them): what the group's padded
shape costs the rule.  None where the program writes no such block."""


def read(facts):
    epochs = facts.get("epochs") or []
    chunks = sum(e.get("gdn_chunks") or 0 for e in epochs)
    padding = sum(e.get("gdn_chunks_padding") or 0 for e in epochs)
    return 100.0 * padding / chunks if chunks else None
