"""Entry + platform resolution: wall seconds of the process's `bls_pubkey_table_load` ledger entries: every `PubkeyTable.extend` (decode, limbs, placement on the lanes), the registry's load and the appends after it."""

TABLE_LOAD = "bls_pubkey_table_load"


def read(ctx):
    loads = [e["seconds"] for e in ctx["all_ledger"] if e["program"] == TABLE_LOAD]
    return sum(loads) if loads else None
