"""Helpers shared across the framework: pytrees (``tree``), host spans
(``trace``), cost and roofline arithmetic."""
