"""Messages the simulated cluster sent (Counters.messages_sent) per
request completed in the window: the timestamp protocol's background
traffic and each request's own."""


def read(run):
    n = run.done_in_window
    sent = run.counters1["messages_sent"] - run.counters0["messages_sent"]
    return sent / n if n else None
