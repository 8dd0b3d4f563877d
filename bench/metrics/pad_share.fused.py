"""Share of the sample slots the train program computes that hold no real
sample: 1 - real / padded, from the program's ``padding_stats`` counts over
the window."""


def read(ctx):
    c = ctx["counts"]
    if not c.get("padded_samples"):
        return None
    return 100.0 * (1.0 - c["real_samples"] / c["padded_samples"])
