"""Device time one decode step spends in the full layers' attention:
median, over the executed ``jit__step`` modules of the trace, of the time
on device operations under ``kv_write`` (the position's key and value
into its page), ``kv_read`` (the reference's gather of pages; nothing
under the kernel, which reads them itself) and ``attn_full`` (the ragged
paged kernel over each stream's own pages), the full layers together.
Scopes as in ``benchmark/program_scopes_kexaone.py``."""

from benchmark import program_scopes_kexaone as scopes


def read(run):
    kt = scopes.of(run)
    return None if kt is None else scopes.decode_ms(kt, scopes.FULL_ATTN_DECODE)
