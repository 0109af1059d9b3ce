"""setup_s: process start to the first timed request (corpus, bring-up,
warm-up and any compilation), on the host clock."""


def read(run):
    return run.setup_s
