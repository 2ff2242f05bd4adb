"""Wall seconds of the first action in the process: what a user's first
query on files the process has not seen pays, compile or cache load
included."""


def read(run):
    return run.first_query_s
