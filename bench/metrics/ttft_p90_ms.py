"""90th percentile of the time to first token, in ms, over every request
due in the window: from when it was due on the open-loop schedule to its
first token, a request unserved at the close counting the close. Above the
knee it is mostly queue wait, which grows through the window."""
from bench import traffic


def read(layer):
    ttft = layer.get("ttft_ms")
    if not ttft:
        return None
    return traffic.percentile(ttft, 90)
