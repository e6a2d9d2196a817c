"""``batch_occupancy`` (layer: serving scheduler): tokens emitted by
decode iterations inside the window over (iterations inside the window x
slots), iterations from ``DecodeEngine.stats()`` at window start and end."""


def read(facts):
    if not facts.get("window_iterations"):
        return None
    return facts["window_decode_tokens"] / (
        facts["window_iterations"] * facts["slots"])
