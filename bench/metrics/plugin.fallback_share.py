"""Share of the plugin compiler's lowerings in this process that fell back
to the XLA composition (``plugin_compiler`` bank: fallback over fused plus
fallback).  Lowerings happen when a program is traced, in set-up."""


def read(run):
    c = run.process_banks.get("plugin_compiler", {})
    total = c.get("fused", 0) + c.get("fallback", 0)
    if not total:
        return None
    return 100.0 * c.get("fallback", 0) / total
