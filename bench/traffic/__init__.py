"""Traffic generators, one module per mix ``kind``; a mix is a JSON file of
parameters beside them, named by the cell's ``traffic``."""
