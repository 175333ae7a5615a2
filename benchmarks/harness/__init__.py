"""The benchmark's own code: nothing here imports the program except
`engine.py`, and the program imports nothing from here."""
