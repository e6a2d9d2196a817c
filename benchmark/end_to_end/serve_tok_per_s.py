"""``serve_tok_per_s``: output tokens emitted inside the window, all
sessions, over the window's seconds.  A token's time is its request's
send time plus the reply's ``ttft_ms`` and ``token_ms`` gaps."""


def read(facts):
    if "window_tokens" not in facts:
        return None
    return facts["window_tokens"] / facts["window_s"]
