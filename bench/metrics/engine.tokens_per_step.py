"""Generated tokens per engine step in the traced window (server loop)."""


def read(run):
    f = run.facts
    if not f.get("engine_steps"):
        return None
    return f["generated_tokens"] / f["engine_steps"]
