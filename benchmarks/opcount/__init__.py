"""Operations and bytes of one kernel call from its shapes, one kernel
to a file: ``count(...)`` and ``shapes_from_hlo(text)``."""
