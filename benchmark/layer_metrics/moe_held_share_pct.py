"""Share of the routed slots of the counted epochs that fell on the experts
this rank holds (the step records' ``moe`` block, as the language-model
drivers sum it): 12.5 is a router that sends 8 of 64 experts their even
share; a rank that trains alone draws load to its own experts, and the
correction bias pushes it back."""


def read(facts):
    epochs = facts.get("epochs") or []
    held = sum(e.get("moe_slots_held") or 0 for e in epochs)
    every = sum(e.get("moe_slots_all") or 0 for e in epochs)
    return 100.0 * held / every if every else None
