"""Process start to the first timed query: relation, build, compiles,
warm-up."""


def read(rec):
    return rec["setup_s"]
