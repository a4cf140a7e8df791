"""One module per ``driver`` a traffic file may name.  A driver owns a whole
run but the printing: ``run(cell, args, t_start, device)`` returns
``correct``, ``attempted``, ``failed``, ``metrics``, ``breakdown`` and
``extra``, so a kind of cell that is not training (the session tier) comes
as a module of its own beside ``train.py``."""
