# launcher package: mesh.py, mine.py, serve_rules.py, stream.py, report.py
