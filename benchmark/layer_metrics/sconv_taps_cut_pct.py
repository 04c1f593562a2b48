"""Share of the taps of real rows that read zero because of a boundary in
the counted epochs (the step records' ``sconv`` block: ``taps_cut`` over
``taps`` x ``rows``, as the short-convolution driver sums them): 3 of a
graph's taps at three taps, so it falls with the documents' length.  None
where the program writes no such block."""


def read(facts):
    epochs = facts.get("epochs") or []
    lm = facts.get("lm") or {}
    taps = (lm.get("sconv") or {}).get("taps")
    rows = sum(e.get("sconv_rows") or 0 for e in epochs)
    cut = sum(e.get("sconv_taps_cut") or 0 for e in epochs)
    return 100.0 * cut / (taps * rows) if taps and rows else None
