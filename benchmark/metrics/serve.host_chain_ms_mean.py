"""What the engine's thread does for one token-step boundary beside waiting for
the device, mean over the engine's life: admission, the launch's inputs, the
dispatch, giving the tokens out, and the time between those spans
(`program_accounts.host_chain_ms`)."""
from benchmark.lib import program_accounts as pa
from benchmark.lib import program_spans as ps


def read(r):
    return pa.host_chain_ms(ps.snapshot())
