"""Traffic generators: each reads a traffic file's parameters and drives
the system under test through one window (see ``base.Generator``)."""
