"""Share of the decode-step program's device time spent in operations under
the model's ``lora`` scope (the ``bgmv`` kernel and the operations around
it), from the trace and the compiled program's metadata
(``op_scopes.scope_times``)."""


def read(ctx):
    scopes = ctx.get("scopes")
    if not scopes or not scopes["decode_s"]:
        return None
    return 100.0 * scopes["by_scope"].get("lora", 0.0) / scopes["decode_s"]
