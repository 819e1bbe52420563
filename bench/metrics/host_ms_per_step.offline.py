"""Host milliseconds per ServeSession.step outside the blocking token
transfer, over the window (the session's wall_s and host_block_s)."""
from readers import host_ms_per_step as read  # noqa: F401
