"""Layer building blocks.  Import each module directly
(``repro.layers.conv``, ``repro.layers.norms``, ...): the package
imports none of them, so the vision path loads only what it uses."""
