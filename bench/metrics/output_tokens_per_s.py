"""output_tokens_per_s: every output token committed in the window,
unfinished requests' included, over the window's length."""


def read(facts):
    return facts["committed_in_window"] / facts["window_s"]
