"""The benchmark's own references and data makers: no program code."""
